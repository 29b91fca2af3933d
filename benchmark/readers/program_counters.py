"""A step counter of the program, or the quotient of two: the sums that
`marian_tpu.obs.TRACER.counters()` holds under `args["num"]` (over
`args["den"]`, times `args["scale"]`). The program counts inside its
jitted step, carries the counts out as lazy outputs and fetches them
where its Scheduler syncs anyway, while a profiler session collects
(obs/trace.py): so the sums cover the traced window. None without a
trace, where the program keeps no such counters (a parent commit without
them), where the counter was never written, or where the divisor is 0."""


def read(obs, args):
    if not obs.get("trace"):
        return None
    try:
        from marian_tpu.obs import TRACER
        counters = TRACER.counters()
    except (ImportError, AttributeError):
        return None
    num = counters.get(args["num"])
    if num is None:
        return None
    if "den" not in args:
        return float(num) * args.get("scale", 1.0)
    den = counters.get(args["den"])
    if not den:
        return None
    return float(num) / float(den) * args.get("scale", 1.0)
