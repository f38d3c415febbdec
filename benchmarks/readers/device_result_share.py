"""Device time of the operations whose kind and result type match
``match`` over the first device's busy time in the traced window, in
percent. ``match`` is a regular expression searched in the operation's
stable name, ``<instruction name without its number> <result type>``
(``fusion f32[32,16,128,128]``): for work that is XLA fusions and no
kernel, found by the shapes only it has. ``readers/device_op_share.py``
matches the instruction's name alone, which a fusion's says nothing by."""

import re

from tracereduce import xplane


def read(args: dict, obs):
    if obs.trace is None:
        return None
    lo, hi = obs.reduction.window
    device = min(obs.trace.devices)
    pattern = re.compile(args["match"])
    hit = [(e.start, e.end) for e in obs.trace.devices[device].ops
           if pattern.search(xplane.stable_name(e.name))]
    busy = obs.reduction.busy_by_device[device]
    seconds = xplane.total(xplane.union(xplane.clip(hit, lo, hi)))
    if not seconds or busy <= 0:
        return None
    return 100.0 * seconds / busy
