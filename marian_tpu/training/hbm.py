"""The device allocator's word for the trainer's memory ledger
(graph_group.py samples it before every live dispatch, scheduler.py at
the display's sync): host integers, one host call, no device op."""

from __future__ import annotations

from typing import Dict, Optional

import jax

# the allocator's statistics (PJRT's names) under the names kept here
_MEMORY_KEYS = (("limit", "bytes_limit"), ("in_use", "bytes_in_use"),
                ("peak", "peak_bytes_in_use"),
                ("reserved", "bytes_reserved"),
                ("largest_free", "largest_free_block_bytes"))


def device_memory() -> Optional[Dict[str, int]]:
    """What the device's allocator says of itself right now: ``limit``,
    ``in_use``, ``peak``, ``reserved`` (on a TPU a running step's
    temporaries are booked there, never under ``in_use``),
    ``largest_free`` (those the runtime gives), of the local device with
    the most in use. None where the devices keep no statistics (the
    CPU)."""
    fullest = None
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if stats and (fullest is None or stats.get("bytes_in_use", 0)
                      > fullest.get("bytes_in_use", 0)):
            fullest = stats
    if fullest is None:
        return None
    return {name: int(fullest[key]) for name, key in _MEMORY_KEYS
            if key in fullest}
