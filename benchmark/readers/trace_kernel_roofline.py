"""A kernel's share of its roofline, in percent: the least time the chip's
peaks allow for the calls' shapes (benchmark/kernel_costs.py, the larger
of operations over peak FLOP/s and bytes over peak bytes/s) over the
kernel's summed device time in the trace. No clamp: a reading over 100
means the cost function or the matching is wrong."""

from benchmark import kernel_costs


def read(obs, args):
    t, work = obs.get("trace"), obs.get("traced_work")
    if not t or not work:
        return None
    spent = sum(t["kernel_s"].get(k, 0.0) for k in args["kernels"])
    if spent <= 0.0:
        return None
    flops, nbytes = getattr(kernel_costs, args["cost"])(work, obs["dims"])
    least, _bound = kernel_costs.roofline_seconds(flops, nbytes, obs["peaks"])
    return 100.0 * least / spent
