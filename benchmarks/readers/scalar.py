"""A number the runner took: ``{"scalar": name, "scale": factor}``."""


def read(args: dict, obs):
    if args["scalar"] not in obs.scalars:
        return None
    return obs.scalars[args["scalar"]] * float(args.get("scale", 1.0))
