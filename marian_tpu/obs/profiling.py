"""``TraceWindow``: the program's ``--profile``, a jax.profiler session
around a window of training updates. While it is open every span of
obs/trace.py is live and lands in the trace's ``/host:CPU`` plane beside
the device ops, on one clock (``cli/profile_summary.py`` reads the
result); window open and close are stamped onto the event timeline.
``common/profiling.py`` re-exports it.
"""

from __future__ import annotations

import os
from typing import Optional

from ..common import logging as log
from .trace import TRACER


class TraceWindow:
    """Capture a jax.profiler trace for updates [start, stop): what the
    chip ran, and the program's spans for the same updates."""

    def __init__(self, options):
        prof = options.get("profile", None)
        self.dir: Optional[str] = None
        # bare `--profile` parses to "" (argparse const) — still means ON
        if prof is not None and prof is not False:
            self.dir = prof if (isinstance(prof, str) and prof) \
                else "profile"
        self.start_update = int(options.get("profile-start", 10) or 10)
        self.n_updates = int(options.get("profile-updates", 5) or 5)
        self._active = False
        self._done = False
        self._started_at = 0

    def tick(self, update: int) -> None:
        """Call once per train-loop update with the 1-based update count."""
        if self.dir is None or self._done:
            return
        import jax
        if not self._active and update >= self.start_update:
            os.makedirs(self.dir, exist_ok=True)
            # host side through TraceMe only: the program's spans and the
            # runtime's. The Python tracer adds an event per call, nested
            # inside every span (a 5.3 ms `train.dispatch` kept 0.1 ms of
            # self time, and cost 1.6 ms more than untraced; my chip
            # runs, PR 24)
            popts = jax.profiler.ProfileOptions()
            popts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=popts)
            self._active = True
            self._started_at = update
            TRACER.event("profile.window_start", update=update,
                         dir=self.dir)
            log.info("Profiler trace started at update {} → {}", update,
                     self.dir)
        elif self._active and update >= self._started_at + self.n_updates:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            TRACER.event("profile.window_stop", update=update)
            log.info("Profiler trace stopped after update {} ({} updates); "
                     "read with python -m marian_tpu.cli.profile_summary {}",
                     update, self.n_updates, self.dir)

    def close(self) -> None:
        if self._active:
            import jax
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            TRACER.event("profile.window_stop", update=-1)
