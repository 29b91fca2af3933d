"""Summarize a jax.profiler trace directory without TensorBoard.

``python -m marian_tpu.cli.profile_summary <trace_dir> [top_n]``

Reads the ``*.xplane.pb`` that ``jax.profiler.start_trace`` writes
(``marian-train --profile``, a capture attached through
``--profile-server``, the benchmark's ``--trace 1``) and prints, per
session: device busy/idle, the top device ops by self time, **device time
by scope** (the ``jax.named_scope`` names of the train step: grads fwd /
grads bwd / optimizer / collectives, then encoder, decoder, ffn, ...),
the host spans by total and self time (the program's ``train.*`` /
``data.*`` spans are TraceMe events there, obs/trace.py), and the idle
gaps of the device named by the innermost program span that covers them
(docs/OBSERVABILITY.md "Reading a profile").

Stdlib only. ``jax.profiler.ProfileData`` walks lines and events but not
the per-op metadata the scope lives in, so this reads the protobuf wire
format directly (tsl/profiler/protobuf/xplane.proto; field numbers below):
on a TPU an op's XEventMetadata carries its HLO ``op_name`` as the stat
``tf_op``; on the CPU an op event names its ``hlo_op`` and ``program_id``
and the ``/host:metadata`` plane holds each program's HLO proto.
"""

import os
import re
import struct
import sys
from collections import defaultdict

OP_LINE = "XLA Ops"
SPAN_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")   # obs spans
# first and second level of the by-scope table; an op belongs to the
# innermost (last) listed name on its name stack
LEVEL1 = ("optimizer", "collectives", "expand_batch")
# collectives the partitioner inserts carry no name stack: known by op
COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute", "reduce_scatter", "psum")
LEVEL2 = ("encoder", "decoder", "embed", "self_attn", "cross_attn", "ffn",
          "pre_post", "output", "loss", "cast", "clip", "adam", "ema",
          # a layer plan's scopes (models/layer_plan.py)
          "kda", "mla", "mla.rope", "gqa", "gqa.rope", "swa", "swa.rope",
          "attn.gate", "conv", "conv.core", "diffusion.noise",
          "experts.route", "experts.compute", "experts.shared", "mtp")


# -- protobuf wire format ------------------------------------------------------

def _varint(buf, i):
    v = shift = 0
    while True:
        c = buf[i]
        i += 1
        v |= (c & 0x7F) << shift
        if c < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire in (1, 2, 5):    # fixed64, length-delimited, fixed32
            ln, i = _varint(buf, i) if wire == 2 else ({1: 8, 5: 4}[wire], i)
            v = buf[i:i + ln]
            i += ln
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, v


def _stats(msgs, stat_names):
    """XStat messages -> {name: value}. XStat: metadata_id=1, double=2,
    uint64=3, int64=4, str=5, bytes=6, ref=7 (a stat_metadata id whose
    NAME is the string)."""
    out = {}
    for m in msgs:
        name, val = None, None
        for f, v in _fields(m):
            if f == 1:
                name = stat_names.get(v, str(v))
            elif f == 2:
                val = struct.unpack("<d", v)[0]
            elif f in (3, 4):
                val = v
            elif f == 5:
                val = bytes(v).decode("utf-8", "replace")
            elif f == 6:
                val = bytes(v)
            elif f == 7:
                val = stat_names.get(v, "")
        out[name] = val
    return out


def read_xspace(path):
    """[plane] of one .xplane.pb; plane = {"name", "lines": [{"name",
    "events": [(start_ps, end_ps, name, event stats, metadata stats)]}]}.
    XSpace.planes=1; XPlane: name=2 lines=3 event_metadata=4 (map)
    stat_metadata=5 (map) ; XLine: name=2 timestamp_ns=3 events=4;
    XEvent: metadata_id=1 offset_ps=2 duration_ps=3 stats=4;
    XEventMetadata: name=2 stats=5; XStatMetadata: name=2."""
    with open(path, "rb") as fh:
        data = memoryview(fh.read())
    planes = []
    for f, pbuf in _fields(data):
        if f != 1:
            continue
        name, lines, emeta_raw, stat_names = "", [], [], {}
        for f2, v in _fields(pbuf):
            if f2 == 2:
                name = bytes(v).decode()
            elif f2 == 3:
                lines.append(v)
            elif f2 == 4:
                emeta_raw.append(v)
            elif f2 == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1, 0)] = bytes(
                    dict(_fields(entry[2])).get(2, b"")).decode()
        emeta = {}
        for raw in emeta_raw:
            entry = dict(_fields(raw))
            ename, smsgs = "", []
            for f3, v in _fields(entry.get(2, b"")):
                if f3 == 2:
                    ename = bytes(v).decode("utf-8", "replace")
                elif f3 == 5:
                    smsgs.append(v)
            emeta[entry.get(1, 0)] = (ename, _stats(smsgs, stat_names))
        plane = {"name": name, "lines": [],
                 "metadata": list(emeta.values())}
        for lbuf in lines:
            lname, t0_ps, events = "", 0, []
            raw_events = []
            for f3, v in _fields(lbuf):
                if f3 == 2:
                    lname = bytes(v).decode()
                elif f3 == 3:
                    t0_ps = v * 1000
                elif f3 == 4:
                    raw_events.append(v)
            for ebuf in raw_events:
                mid = off = dur = 0
                smsgs = []
                for f4, v in _fields(ebuf):
                    if f4 == 1:
                        mid = v
                    elif f4 == 2:
                        off = v
                    elif f4 == 3:
                        dur = v
                    elif f4 == 4:
                        smsgs.append(v)
                ename, mstats = emeta.get(mid, ("?", {}))
                events.append((t0_ps + off, t0_ps + off + dur, ename,
                               _stats(smsgs, stat_names), mstats))
            plane["lines"].append({"name": lname, "events": events})
        planes.append(plane)
    return planes


def hlo_op_names(planes):
    """{(program id, instruction name): op_name} from the HLO protos of
    the /host:metadata plane. HloProto.hlo_module=1; HloModuleProto
    .computations=3; HloComputationProto.instructions=2;
    HloInstructionProto: name=1 metadata=7; OpMetadata.op_name=2."""
    out = {}
    for plane in planes:
        if plane["name"] != "/host:metadata":
            continue
        for ename, mstats in plane["metadata"]:
            proto = mstats.get("Hlo Proto")
            m = re.search(r"\((\d+)\)$", ename)
            if not proto or not m:
                continue
            module = dict(_fields(memoryview(proto))).get(1, b"")
            for f, comp in _fields(module):
                if f != 3:
                    continue
                for f2, ins in _fields(comp):
                    if f2 != 2:
                        continue
                    d = dict(_fields(ins))
                    op = dict(_fields(d.get(7, b""))).get(2, b"")
                    out[(int(m.group(1)), bytes(d.get(1, b"")).decode())] \
                        = bytes(op).decode("utf-8", "replace")
    return out


# -- reduction -----------------------------------------------------------------

def op_name(event_name):
    """'%fusion.12 = f32[..] fusion(..)' -> 'fusion': the instruction's
    name without its numbering, so like ops add up."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[._]*\d*$", "", head) or head


def scope_of(stack, op=""):
    """(first level, second level) of an op's name stack, e.g.
    'jit(one_update)/grads/transpose(jvp(decoder))/ffn/dot_general' ->
    ('grads bwd', 'decoder/ffn'). Autodiff wraps the components it
    differentiated: jvp(..) marks the forward, transpose(..) the
    backward ops."""
    parts, depth, cur = [], 0, ""
    for ch in stack.rstrip(":"):
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur += ch
    parts.append(cur)
    names = [re.sub(r"^(?:\w+\()+|\)+$", "", p) for p in parts]
    first = next((n for n in names if n in LEVEL1), None)
    if first is None:
        if op.startswith(COLLECTIVE_OPS):
            first = "collectives"
        elif any(p.startswith("transpose(") for p in parts):
            first = "grads bwd"
        elif any(p.startswith("jvp(") for p in parts):
            first = "grads fwd"
        elif "grads" in names:
            first = "grads (neither)"
        else:
            first = "other"
    inner = [n for n in names if n in LEVEL2]
    second = "/".join(dict.fromkeys(
        [n for n in inner if n in ("encoder", "decoder")][:1] + inner[-1:]))
    return first, second or "-"


def union_ps(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def self_times(events):
    """[(start, end, key)] of ONE line, possibly nested -> [(key, self)]:
    duration minus what directly nested events cover."""
    out, stack = [], []
    for s, e, key in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[2], done[1] - done[0] - done[3]))
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, key, 0])
    out.extend((d[2], d[1] - d[0] - d[3]) for d in stack)
    return out


def device_ops(planes):
    """[[(start, end, (instruction name, name stack))]] per device: the
    'XLA Ops' line of each TPU plane, else (a CPU trace) the host lines
    whose events name an hlo_op."""
    hlo = None
    per_device = []
    for plane in planes:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        for line in plane["lines"]:
            if line["name"] != OP_LINE:
                continue
            evs = []
            for s, e, name, _st, mst in line["events"]:
                stack = mst.get("tf_op")
                if stack is None:
                    hlo = hlo if hlo is not None else hlo_op_names(planes)
                    stack = hlo.get((mst.get("program_id"), name.split(
                        " = ", 1)[0].lstrip("%")), "")
                evs.append((s, e, (op_name(name), stack)))
            if evs:
                per_device.append(evs)
    if per_device:
        return per_device
    evs = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for s, e, name, st, _mst in line["events"]:
                if "hlo_op" in st and e > s:
                    hlo = hlo if hlo is not None else hlo_op_names(planes)
                    evs.append((s, e, (op_name(name), hlo.get(
                        (st.get("program_id"), st["hlo_op"]), ""))))
    return [evs] if evs else []


def host_lines(planes):
    """[[(start, end, name)]] per host thread, XLA's own op events left
    out (they are the 'device' of a CPU trace)."""
    out = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            evs = [(s, e, name) for s, e, name, st, _ in line["events"]
                   if e > s and "hlo_op" not in st]
            if evs:
                out.append(evs)
    return out


def name_gap(gap, host):
    """The innermost program span covering at least half of an idle gap;
    failing that the innermost host event that does; failing that the
    host event overlapping it most."""
    gs, ge = gap
    best = {True: None, False: None}
    most = ("(no host span)", 0)
    for s, e, name in host:
        ov = min(e, ge) - max(s, gs)
        if ov <= 0:
            continue
        if 2 * ov >= ge - gs:
            k = bool(SPAN_RE.match(name))
            if best[k] is None or e - s < best[k][1]:
                best[k] = (name, e - s)
        if ov > most[1]:
            most = (name, ov)
    return (best[True] or best[False] or most)[0]


def summarize_file(path, top_n):
    planes = read_xspace(path)
    per_device = device_ops(planes)
    host = host_lines(planes)
    ms = 1e-9                                    # ps -> ms
    print(f"== {path}")
    if not per_device:
        print("no device op in this trace")
    ops, scopes, op_scopes, busy, span, gaps = {}, {}, {}, 0, 0, []
    for i, evs in enumerate(per_device):
        lo, hi = min(s for s, _, _ in evs), max(e for _, e, _ in evs)
        b, merged = union_ps([(s, e) for s, e, _ in evs])
        busy, span = busy + b, span + hi - lo
        for (op, stack), t in self_times(evs):
            ops[op] = ops.get(op, 0) + t
            key = scope_of(stack, op)
            scopes[key] = scopes.get(key, 0) + t
            where = op_scopes.setdefault(op, {})
            where[key] = where.get(key, 0) + t
        if i == 0:
            edges = [x for iv in merged for x in iv][1:-1]
            gaps = list(zip(edges[::2], edges[1::2]))
    if per_device:
        print(f"devices {len(per_device)}; op span {span * ms:.1f} ms; "
              f"device busy {busy * ms:.1f} ms = {100 * busy / span:.1f}% "
              f"-> idle {100 * (1 - busy / span):.1f}%")
        total = sum(ops.values())
        print(f"\ntop device ops by self time ({len(ops)} names, "
              f"{total * ms:.1f} ms):")
        for op, t in sorted(ops.items(), key=lambda kv: -kv[1])[:top_n]:
            # and the scopes most of it belongs to
            where = sorted(op_scopes[op].items(), key=lambda kv: -kv[1])[:3]
            print(f"{t * ms:10.2f} ms {100 * t / total:6.2f}%  {op[:40]:<28}"
                  + "  ".join(f"{f1}{'' if f2 == '-' else ' ' + f2} "
                              f"{100 * w / t:.0f}%"
                              for (f1, f2), w in where))
        print("\ndevice time by scope (self time; % of busy):")
        level1 = defaultdict(int)
        for (first, _), t in scopes.items():
            level1[first] += t
        for first, t1 in sorted(level1.items(), key=lambda kv: -kv[1]):
            print(f"{t1 * ms:10.2f} ms {100 * t1 / total:6.2f}%  {first}")
            inner = sorted(((sec, t) for (f1, sec), t in scopes.items()
                            if f1 == first), key=lambda kv: -kv[1])
            for sec, t in inner:
                if sec != "-" or len(inner) > 1:
                    print(f"{t * ms:14.2f} ms {100 * t / total:6.2f}%    "
                          f"{sec if sec != '-' else '(no inner scope)'}")
    tot, slf, cnt = defaultdict(int), defaultdict(int), defaultdict(int)
    for evs in host:
        for s, e, name in evs:
            tot[name] += e - s
            cnt[name] += 1
        for name, t in self_times(evs):
            slf[name] += t
    rows = sorted(tot, key=lambda n: (not SPAN_RE.match(n), -slf[n]))
    print(f"\nhost spans (program spans first; then by self time):\n"
          f"{'total ms':>10} {'self ms':>10} {'calls':>7}  span")
    for name in rows[:top_n]:
        print(f"{tot[name] * ms:10.2f} {slf[name] * ms:10.2f} "
              f"{cnt[name]:7d}  {name[:80]}")
    if gaps:
        flat = [ev for evs in host for ev in evs]
        by_cause = defaultdict(int)
        for g in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
            by_cause[name_gap(g, flat)] += g[1] - g[0]
        print(f"\nidle gaps of device 0 ({len(gaps)}, "
              f"{sum(e - s for s, e in gaps) * ms:.2f} ms) by innermost "
              f"program span:")
        for name, t in sorted(by_cause.items(),
                              key=lambda kv: -kv[1])[:top_n]:
            print(f"{t * ms:10.2f} ms  {name[:80]}")
    return bool(per_device)


def summarize(trace_dir, top_n=25):
    paths = sorted(os.path.join(d, f) for d, _dirs, files in
                   os.walk(trace_dir) for f in files
                   if f.endswith(".xplane.pb"))
    if os.path.isfile(trace_dir):
        paths = [trace_dir]
    if not paths:
        print(f"no *.xplane.pb under {trace_dir} — run with --profile "
              f"first", file=sys.stderr)
        return 1
    # one file per profiling session: each is summarized on its own, so
    # the idle time BETWEEN sessions never reads as a host gap
    return 0 if all([summarize_file(p, top_n) for p in paths]) else 1


def main():
    if len(sys.argv) < 2:
        print(__doc__, file=sys.stderr)
        raise SystemExit(2)
    raise SystemExit(summarize(
        sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25))


if __name__ == "__main__":
    main()
