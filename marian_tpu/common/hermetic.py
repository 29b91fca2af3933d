"""Virtual-CPU platform setup, shared by tests/conftest.py,
__graft_entry__.dryrun_multichip and bench_decode.py's explicit-CPU smoke.

Tests and dry runs never touch the chip: they run on N virtual CPU devices
(Pallas kernels in interpret mode), so multi-device sharding logic is
testable on a machine with no accelerator. The chip is reached only by
running a normal entry point where JAX finds it (`python chip_smoke.py`
through the chip tool) — nothing here selects or hides it.
"""

from __future__ import annotations

import os
import re


def force_cpu_devices(n_devices: int):
    """Force jax onto a CPU platform with at least ``n_devices`` devices.

    Call it before the process first uses a jax backend; a second call is
    fine when the first already provisioned enough. Returns the jax module.
    Raises RuntimeError if the platform cannot be provisioned (never
    silently under-provisions — a 1-device run must not report success
    for an 8-device request).
    """
    # Honor a larger preexisting override (e.g. a developer running the
    # suite at 16 devices) — only ever grow the count.
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    want = max(n_devices, int(m.group(1)) if m else 0)
    flag = f"--xla_force_host_platform_device_count={want}"
    if m:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    # the environment is for child processes (and for jax, if this is its
    # first import); the config updates are for this process
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_ENABLE_X64", "0")

    import jax

    jax.config.update("jax_platforms", "cpu")
    if jax.config.jax_num_cpu_devices < want:
        try:
            jax.config.update("jax_num_cpu_devices", want)
        except RuntimeError:
            # a backend already exists with too few devices (a dry run
            # called after single-device work in the same process):
            # rebuild the CPU client at the requested count
            import jax.extend.backend as eb
            eb.clear_backends()
            jax.config.update("jax_num_cpu_devices", want)

    devs = jax.devices()
    if len(devs) < n_devices or devs[0].platform != "cpu":
        raise RuntimeError(
            f"virtual CPU setup failed: got {len(devs)} "
            f"{devs[0].platform if devs else '?'} devices, "
            f"need {n_devices} cpu devices")
    return jax
