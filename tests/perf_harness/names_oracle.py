"""The kernels' device time as the benchmark totalled it UNTIL PR 51, kept
as an oracle: by exact pallas_call name, in seconds added up event by
event. `benchmark/trace_reduce.py` now totals kernel FAMILIES; on a step
whose kernels are the ones named here a family's total is the sum of its
names' totals, and `test_kernel_families.py` and `reduce_both_ways.py`
(on the chip) hold the new reduction to that.

`kernel_s_by_name` is the parent's `reduce_trace` (commit d9ac2ec) with
everything but `kernel_s` left out; what it shares with the new reducer
is what PR 51 did not touch (the window, the clipping, `kernel_of`).
"""

from benchmark import trace_reduce

# the names `configs/*.json::kernels` and the five `*_roofline.json` held
# until PR 51, under the family that stands there now, in the files' order
NAMES_UNTIL_PR51 = {
    "packed_attention": ("packed_attention_fwd", "packed_attention_bwd"),
    "flash_attention": ("flash_attention_fwd", "flash_attention_dq",
                        "flash_attention_dkv"),
    "fused_ce": ("fused_ce_fwd", "fused_ce_dx", "fused_ce_dw"),
    "kda_chunk": ("kda_chunk_fwd", "kda_chunk_bwd"),
}
# the parent's KNOWN_KERNELS
KNOWN_NAMES = ("packed_attention_fwd", "packed_attention_bwd",
               "flash_attention_fwd", "flash_attention_dq",
               "flash_attention_dkv", "fused_ce_fwd", "fused_ce_dx",
               "fused_ce_dw", "decode_attention", "paged_decode_attention")


def names_of(families):
    """The names that stood for these families, in the files' order."""
    return tuple(n for f in families for n in NAMES_UNTIL_PR51.get(f, (f,)))


def kernel_s_by_name(path, kernels):
    """`kernel_s` of the parent's reduce_trace(path, kernels)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    candidates = tuple(set(KNOWN_NAMES) | set(kernels))   # names win: longer
    host = trace_reduce._host_events(pd)
    window = None
    for s, e, name in host:
        if name == trace_reduce.WINDOW_SPAN and (
                window is None or e - s > window[1] - window[0]):
            window = (s, e)
    per_device = []
    for plane in trace_reduce._device_planes(pd):
        lines = [l for l in plane.lines if l.name == trace_reduce.OP_LINE]
        events = [(e.start_ns, e.start_ns + e.duration_ns,
                   trace_reduce.op_name(e.name))
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
        lo, hi = window
    n = len(per_device)
    kernel_s = {k: 0.0 for k in kernels}
    for events in per_device:
        events = [(max(s, lo), min(e, hi), name) for s, e, name in events
                  if min(e, hi) > max(s, lo)]
        for s, e, name in events:
            k = trace_reduce.kernel_of(name, candidates)
            if k in kernel_s:
                kernel_s[k] += (e - s) / 1e9 / n
    return kernel_s


def roofline_by_name(obs, args):
    """`readers/trace_kernel_roofline.py::read` as the parent had it, over
    the names that stood in the metric's file: obs["trace"]["kernel_s"]
    is `kernel_s_by_name`'s."""
    from benchmark import kernel_costs, manifest
    spent = sum(obs["trace"]["kernel_s"].get(k, 0.0)
                for k in names_of(args["kernels"]))
    if spent <= 0.0:
        return None
    flops, nbytes = manifest.load_cost(args["cost"], obs.get("root"))(
        obs["traced_work"], obs["dims"])
    least, _ = kernel_costs.roofline_seconds(flops, nbytes, obs["peaks"])
    return 100.0 * least / spent
