"""A kernel's share of its roofline, in percent: the least time the chip's
peaks allow for the calls' shapes (the metric file's `cost`, a function of
benchmark/kernel_costs.py or `module:function`; the larger of operations
over peak FLOP/s and bytes over peak bytes/s) over the summed device time
of the kernel FAMILIES the metric file names (`args.kernels`; whichever
kernels implement a family are charged the same work). A family that has
left the step has no time and the reader returns nothing. No clamp: a
reading over 100 means the cost function or the matching is wrong."""

from benchmark import kernel_costs, manifest


def read(obs, args):
    t, work = obs.get("trace"), obs.get("traced_work")
    if not t or not work:
        return None
    spent = sum(t["kernel_s"].get(k, 0.0) for k in args["kernels"])
    if spent <= 0.0:
        return None
    cost = manifest.load_cost(args["cost"], obs.get("root"))
    flops, nbytes = cost(work, obs["dims"])
    least, _bound = kernel_costs.roofline_seconds(flops, nbytes, obs["peaks"])
    return 100.0 * least / spent
