"""Device time of the operations whose HLO name matches ``match`` on the
first device, per traced program step: ``{"match": regex, "scale": f}``.
The part of it with no other operation running goes on an earlier line."""

import harness
from tracereduce import xplane


def read(args: dict, obs):
    if obs.trace is None or not obs.traced_steps:
        return None
    hit = xplane.matching(obs.trace, args["match"], obs.reduction.window)
    if not hit["count"]:
        return None
    scale = float(args.get("scale", 1.0))
    harness.say("metric", match=args["match"],
                per_step=hit["total"] / obs.traced_steps * scale,
                exposed_per_step=hit["exposed"] / obs.traced_steps * scale,
                events_per_step=hit["count"] / obs.traced_steps)
    return hit["total"] / obs.traced_steps * scale
