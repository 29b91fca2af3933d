"""Share of one host-clock span in another (by default the window), in
percent; `complement` gives 100 minus it. Also serves exact count ratios
(real over padded tokens): the arithmetic is the same."""


def read(obs, args):
    values = obs.get("values", {})
    num, den = values.get(args["span"]), values.get(args.get("of", "window_s"))
    if num is None or not den:
        return None
    share = 100.0 * float(num) / float(den)
    return 100.0 - share if args.get("complement") else share
