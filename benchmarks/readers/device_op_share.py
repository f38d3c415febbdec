"""Device time of the operations whose HLO name matches ``match`` over the
first device's busy time in the traced window, in percent."""

from tracereduce import xplane


def read(args: dict, obs):
    if obs.trace is None:
        return None
    hit = xplane.matching(obs.trace, args["match"], obs.reduction.window)
    busy = obs.reduction.busy_by_device[min(obs.reduction.busy_by_device)]
    if not hit["count"] or busy <= 0:
        return None
    return 100.0 * hit["total"] / busy
