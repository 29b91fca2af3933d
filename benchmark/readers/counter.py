"""A count or span the driver kept: values[name] * scale."""


def read(obs, args):
    v = obs.get("values", {}).get(args["name"])
    return None if v is None else float(v) * args.get("scale", 1.0)
