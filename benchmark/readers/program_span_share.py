"""Share of the traced window that the program's own spans cover, in
percent: the seconds (`"self": true`: the self seconds, a span's duration
less what its child spans on the same thread cover) that
`marian_tpu.obs.TRACER.totals()` holds for `args["spans"]`, over the traced
window. The program's spans are live exactly while a profiler session
collects (obs/trace.py), so the totals were gathered over the stretch the
host span `bench.window` marks. None without a trace, and None where the
program keeps no totals (a parent commit without them)."""


def read(obs, args):
    t = obs.get("trace")
    if not t or not t.get("window_s"):
        return None
    try:
        from marian_tpu.obs import TRACER
        totals = TRACER.totals()
    except (ImportError, AttributeError):
        return None
    if not totals:
        return None
    key = "self_seconds" if args.get("self") else "seconds"
    spent = sum(totals[n][key] for n in args["spans"] if n in totals)
    return 100.0 * spent / t["window_s"]
