"""Self time of the package's spans per program step in the window:
``{"spans": [names], "scale": factor}`` (seconds times ``scale``)."""


def read(args: dict, obs):
    found = [n for n in args["spans"] if n in obs.span_self_s]
    if not found or not obs.steps:
        return None
    total = sum(obs.span_self_s[n] for n in found)
    return total / obs.steps * float(args.get("scale", 1.0))
