"""From a profiler trace (.xplane.pb) to the numbers per-layer metrics read:
device busy seconds (union of op intervals), the traced window, per-op and
per-kernel device time, and the longest idle gaps named by what the host
was doing. Only jax.profiler.ProfileData is needed.

What a TPU trace looks like (looked at by hand, PR 23): one plane per chip
named "/device:TPU:<n>", whose line "XLA Ops" holds one event per executed
HLO op (nested for control flow: a `while` event covers its body's ops),
beside lines "XLA Modules" (one event per program run), "Async XLA Ops"
and "TC Overlay". An op event's name is the whole HLO instruction text,
"%fusion.12 = f32[...] fusion(...)"; the instruction's own name comes
first, and a Pallas kernel's is made from its pallas_call `name=`
("%fixture_kernel.1", "%jvp_packed_attention_fwd_.31"). A kernel is told
by the FAMILY its name holds (`flash_attention` in `flash_attention_dkv`),
never by the name itself: which kernels implement a family is the
program's to change (PR 51). Host threads are
lines of the "/host:CPU" plane; TraceAnnotation spans (the drivers'
`bench.*`) are events on the thread that opened them. In the recorded
fixture the device's events lie about 1 ms EARLIER than the host calls
that launched them: the two clocks agree to about a millisecond, no
better, so a gap shorter than that may be named for the wrong span.
"""

import glob
import math
import os
import re

WINDOW_SPAN = "bench.window"
OP_LINE = "XLA Ops"
# every kernel family of the program (its pallas_call names hold one
# each): an op belongs to the LONGEST of these its name holds, so
# `decode_attention` never claims a `paged_decode_attention` op, and
# `kda_prep` is here so that it is told from `kda_chunk`
KNOWN_KERNELS = ("packed_attention", "flash_attention", "fused_ce",
                 "kda_chunk", "kda_prep", "decode_attention",
                 "paged_decode_attention")


def op_name(event_name):
    """'%fusion.12 = f32[..] fusion(..)' -> 'fusion': the instruction's
    name without its numbering, so like ops add up in the breakdown."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[._]*\d*$", "", head) or head


def kernel_of(name, families=()):
    """The longest family the op's or kernel's name holds, of `families`
    and KNOWN_KERNELS, or None."""
    held = [k for k in (*KNOWN_KERNELS, *families) if k in name]
    return max(held, key=len) if held else None


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def union_seconds(intervals):
    """Total length of the union of [start, end) intervals (ns) in s, and
    the merged intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def self_times(events):
    """events: [(start, end, name)] of ONE line, possibly nested. Returns
    [(name, self_ns)]: duration minus what directly nested events cover."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -(ev[1]))):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[2], done[1] - done[0] - done[3]))
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, name, 0])
    while stack:
        done = stack.pop()
        out.append((done[2], done[1] - done[0] - done[3]))
    return out


def _device_planes(pd):
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not planes:       # a CPU-recorded rehearsal trace has no device plane
        planes = [p for p in pd.planes if p.name.startswith("/device:")]
    return planes


def _host_events(pd):
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return out


def _name_gap(gap, host):
    """What the host was doing during an idle gap: the shortest (the
    innermost) host span that covers at least half of it; failing that,
    the span that overlaps it most."""
    gs, ge = gap
    inner, most = None, ("(no host span)", 0)
    for s, e, name in host:
        ov = min(e, ge) - max(s, gs)
        if ov <= 0:
            continue
        if 2 * ov >= ge - gs and (inner is None or e - s < inner[1]):
            inner = (name, e - s)
        if ov > most[1]:
            most = (name, ov)
    return inner[0] if inner else most[0]


def reduce_trace(path, kernels=()):
    """Reduce one .xplane.pb. `kernels`: the kernel families to total,
    each the device time of every op whose name holds it (added with
    `math.fsum`, so a total depends neither on the events' order nor on
    how many kernels share it: the trace's whole nanoseconds add up
    exactly, and a family's total IS the sum of its kernels'). Returns
    None when the trace holds no device op at all."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host = _host_events(pd)
    window = None
    for s, e, name in host:
        if name == WINDOW_SPAN and (window is None
                                    or e - s > window[1] - window[0]):
            window = (s, e)
    per_device = []
    for plane in _device_planes(pd):
        lines = [l for l in plane.lines if l.name == OP_LINE]
        events = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                  for l in lines[:1] for e in l.events if e.duration_ns > 0]
        if events:
            per_device.append(events)
    if not per_device:
        return None
    lo = min(ev[0] for evs in per_device for ev in evs)
    hi = max(ev[1] for evs in per_device for ev in evs)
    if window is not None and any(
            min(e, window[1]) > max(s, window[0])
            for evs in per_device for s, e, _ in evs):
        lo, hi = window          # host and device clocks agree: use the span
    n = len(per_device)
    busy = 0.0
    ops, gaps = {}, []
    kernel_ns = {k: [] for k in kernels}
    for i, events in enumerate(per_device):
        # everything below is of the window only: events clipped to it
        events = [(max(s, lo), min(e, hi), name) for s, e, name in events
                  if min(e, hi) > max(s, lo)]
        b, merged = union_seconds([(s, e) for s, e, _ in events])
        busy += b / n
        for name, ns in self_times(events):
            ops[name] = ops.get(name, 0.0) + ns / 1e9 / n
        for s, e, name in events:
            k = kernel_of(name, kernels)
            if k in kernel_ns:
                kernel_ns[k].append(e - s)
        if i == 0:       # gaps of the first chip stand for all
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    gaps.sort(key=lambda g: g[0] - g[1])
    by_cause = {}
    for g in gaps[:200]:
        cause = _name_gap(g, host)
        by_cause[cause] = by_cause.get(cause, 0.0) + (g[1] - g[0]) / 1e9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(by_cause.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "n_devices": n,
        "kernel_s": {k: math.fsum(ns) / 1e9 / n
                     for k, ns in kernel_ns.items()},
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in top_gaps],
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
        "window_from": "host span" if (window is not None
                                        and (lo, hi) == window)
        else "device events",
    }

