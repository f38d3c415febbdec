"""A kernel's share of its roofline: the least time the chip could take
for the operations and bytes the algorithm needs (``work.<function>``,
from shapes and the slots' true lengths, and ``peaks.json``) over the
kernel's device time in the trace, in percent. Says which bound holds."""

import harness
from readers import work
from tracereduce import xplane


def read(args: dict, obs):
    if obs.trace is None or obs.peaks is None:
        return None
    hit = xplane.matching(obs.trace, args["match"], obs.reduction.window)
    needs = getattr(work, args["work"])(obs)
    if not hit["count"] or needs is None:
        return None
    flops, nbytes = needs
    t_compute = flops / obs.peaks["bf16_flops_per_s"]
    t_memory = nbytes / obs.peaks["hbm_bytes_per_s"]
    harness.say("metric", kernel=args["match"], flops=flops, bytes=nbytes,
                least_s=max(t_compute, t_memory), kernel_s=hit["total"],
                bound="memory" if t_memory >= t_compute else "compute")
    return 100.0 * max(t_compute, t_memory) / hit["total"]
