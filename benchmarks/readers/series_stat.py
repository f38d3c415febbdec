"""A statistic of a series the runner kept: ``{"series": name, "stat":
"median" | "mean" | "p90" | "sum", "scale": factor}``."""

import harness


def read(args: dict, obs):
    values = obs.series.get(args["series"])
    if not values:
        return None
    return harness.stat(values, args["stat"]) * float(args.get("scale", 1.0))
