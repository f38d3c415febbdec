"""Device time of the operations whose instruction text matches ``match``
over the first device's busy time in the traced window, in percent.
``match`` is a regular expression searched in the whole of the HLO
instruction as the profiler names the event: the result's type and every
operand's (``%fusion.386 = bf16[1,128,2048]{..} fusion(bf16[1,128,2048]{..}
%get-tuple-element.1216, .., bf16[6,64,1536,2048]{..} %get-tuple-element.1327,
..), kind=kOutput, ..``). For work whose result has a type that other work
has too and whose operand has not: a matmul told by the weight stack it
streams. A container (``while``, ``conditional``, ``call``) spans its
body's operations and carries their operands through: it is left out, as
the time per operation leaves it out. ``readers/device_result_share.py``
sees the result's type alone."""

import re

from tracereduce import xplane


def read(args: dict, obs):
    if obs.trace is None:
        return None
    lo, hi = obs.reduction.window
    device = min(obs.trace.devices)
    pattern = re.compile(args["match"])
    hit = [(e.start, e.end) for e in obs.trace.devices[device].ops
           if xplane.op_kind(e.name) not in xplane.CONTAINERS
           and pattern.search(e.name)]
    busy = obs.reduction.busy_by_device[device]
    seconds = xplane.total(xplane.union(xplane.clip(hit, lo, hi)))
    if not seconds or busy <= 0:
        return None
    return 100.0 * seconds / busy
