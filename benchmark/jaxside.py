"""What the process that holds the chip needs in every driver: the device
check, the compile cache, compile events with their times, the kernels in
the programs JAX hands the compiler, peak memory, and the trace window.
Imports JAX: a parent that must stay off the chip does not import this.
"""

import os
import re
import shutil
import sys
import tempfile
import time

from benchmark import trace_reduce

EXIT_NO_CHIP = 4
# kernel_name = "<name>" in the StableHLO JAX dumps for each program
# (JAX_DUMP_IR_TO; written before the persistent cache is asked, so a warm
# cache hides none) — chip_smoke.py's way of finding kernels
KERNEL_RE = re.compile(r'kernel_name = "([A-Za-z0-9_]+)"')


def arm_ir_dump():
    """Point JAX_DUMP_IR_TO at a fresh directory under TMPDIR. Call before
    importing jax."""
    d = tempfile.mkdtemp(prefix="bench_ir_")
    os.environ["JAX_DUMP_IR_TO"] = d
    return d


def lift_cache_cap():
    """Take a size cap off the persistent compile cache for this process,
    and say so on stderr. Call before importing jax.

    Where the machine sets JAX_COMPILATION_CACHE_MAX_SIZE (192 MiB on the
    chip tool's) JAX evicts least-recently-used entries, and a train
    cell's seven step programs of ~25 MB each evict one another in turn:
    EVERY run then compiled for ten minutes (my chip runs, PR 23), which
    no run of a check survives. The contract wants every program of a
    cell in the cache after its first run, so the cap cannot stand while
    the cell runs; the directory stays the machine's own."""
    cap = os.environ.get("JAX_COMPILATION_CACHE_MAX_SIZE", "-1")
    if cap != "-1":
        print(f"benchmark: JAX_COMPILATION_CACHE_MAX_SIZE={cap} lifted for "
              "this process: a cell's programs must all stay cached",
              file=sys.stderr, flush=True)
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def kernels_dumped(ir_dir):
    """The named kernels in the programs dumped so far; drops the dump."""
    found = set()
    if ir_dir and os.path.isdir(ir_dir):
        for name in os.listdir(ir_dir):
            with open(os.path.join(ir_dir, name), errors="replace") as fh:
                found.update(KERNEL_RE.findall(fh.read()))
        shutil.rmtree(ir_dir, ignore_errors=True)
    return found


def require_devices(chips, rehearse):
    """The cell's chips or no result: exits non-zero when JAX finds another
    platform than a TPU or another count than the cell asks for."""
    import jax
    devs = jax.devices()
    if rehearse:
        return devs[:chips] if len(devs) >= chips else devs
    if devs[0].platform != "tpu" or len(devs) != chips:
        print(f"benchmark: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} x {devs[0].platform!r} — no result",
              file=sys.stderr, flush=True)
        sys.exit(EXIT_NO_CHIP)
    return devs


def enable_cache():
    from marian_tpu.common.profiling import enable_compilation_cache
    enable_compilation_cache()


class CompileLog:
    """Every backend compile (or cache load) with the time it ended."""

    def __init__(self):
        import jax.monitoring
        self.events = []     # (time.time() at the event, seconds it took)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.events.append((time.time(), float(secs)))

    def count_between(self, t0, t1):
        return sum(1 for t, _ in self.events if t0 <= t <= t1)

    def total_s(self):
        return sum(s for _, s in self.events)


def device_info(devs):
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class TraceWindow:
    """A profiler trace of a few seconds inside the measured window. The
    host span `bench.window` marks its extent on the profiler's clock."""

    def __init__(self, enabled, after_s, for_s):
        self.enabled, self.after_s, self.for_s = enabled, after_s, for_s
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if enabled else None
        self.state = "idle" if enabled else "done"
        self._span = None
        self.t_on = self.t_off = None
        # seconds start_trace/stop_trace themselves took: the device is
        # drained around both, so the drivers take them out of the window
        self.overhead_s = 0.0

    def due(self, now, t0):
        """Whether tick() would start or stop the trace now."""
        return ((self.state == "idle" and now - t0 >= self.after_s)
                or (self.state == "on" and now - self.t_on >= self.for_s))

    def tick(self, now, t0):
        """Call at a step boundary with the device drained: starts the
        trace once `after_s` of the window have passed, stops it `for_s`
        later."""
        import jax
        if not self.due(now, t0):
            return
        if self.state == "idle":
            began = time.perf_counter()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans via TraceMe only
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            self._span.__enter__()
            self.state, self.t_on = "on", time.perf_counter()
            self.overhead_s += self.t_on - began
        else:
            self.stop()

    def stop(self):
        import jax
        if self.state == "on":
            self._span.__exit__(None, None, None)
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()
            self.overhead_s += time.perf_counter() - self.t_off
            self.state = "done"

    def reduce(self, kernels):
        if not self.enabled or self.t_off is None:
            return None
        path = trace_reduce.find_xplane(self.dir)
        out = trace_reduce.reduce_trace(path, kernels) if path else None
        shutil.rmtree(self.dir, ignore_errors=True)
        return out
