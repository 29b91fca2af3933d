"""A gauge of the program, or its quotient over another: what
`marian_tpu.obs.TRACER.gauges()` holds under `args["gauge"]`, its
`args["stat"]` (`min`, `max` or `last`), over the `last` of
`args["over"]` where given, times `args["scale"]`. The trainer samples
the device allocator's word into gauges while a profiler session collects
(the free bytes before every dispatch, the drained set at every sync:
obs/trace.py, training/graph_group.py), so a minimum is the traced
window's. None without a trace, where the program keeps no gauges (a
parent commit without them), where the gauge was never written (a device
without memory statistics), or where the divisor is 0."""


def read(obs, args):
    if not obs.get("trace"):
        return None
    try:
        from marian_tpu.obs import TRACER
        gauges = TRACER.gauges()
    except (ImportError, AttributeError):
        return None
    num = gauges.get(args["gauge"])
    if num is None:
        return None
    value = float(num[args["stat"]])
    if "over" in args:
        den = gauges.get(args["over"])
        if not den or not den["last"]:
            return None
        value /= float(den["last"])
    return value * args.get("scale", 1.0)
