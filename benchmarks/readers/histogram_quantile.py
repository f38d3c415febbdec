"""A percentile of one of the package's histograms, over the samples it
kept since the window began: ``{"histogram": name, "q": 90, "scale": f}``."""

import harness


def read(args: dict, obs):
    samples = obs.histograms.get(args["histogram"])
    if not samples:
        return None
    return harness.percentile(samples, float(args["q"])) * float(
        args.get("scale", 1.0))
