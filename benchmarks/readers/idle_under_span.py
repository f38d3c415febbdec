"""Of the first device's idle seconds inside the traced window, the share
that lies under the program's own spans of the given names, in percent:
``{"spans": [names]}``.

Idle is the window less the union of the device's operations, as
``xplane.reduce`` takes it. The spans are the tracer's (``obs.get_tracer()``,
microseconds of ``perf_counter``), which the trace does not hold: they are
moved onto the trace's host clock by the shift the runner gave the
``engine/`` spans when it appended them to ``trace.annotations``, recovered
from the ``engine/`` events that are in both (same name, same duration).
Nothing to read with no device trace, with no such pair, or in a program
whose tracer has no witness of the host (``watch_host``): its silence would
read as 0."""

from collections import Counter

from tracereduce import xplane

PAIR_PREFIX = "engine/"
# the runner's spans are placed to the microsecond and better
SAME_S = 1e-6


def shift_of(annotations, events):
    """Seconds to add to a tracer event's ``ts * 1e-6`` to land on the
    trace's clock: the one value that most of the first ``engine/``
    annotations agree on with a tracer event of their name and duration;
    ``None`` where no annotation finds such an event."""
    by_name = {}
    for ev in events:
        if ev.get("ph") == "X" and ev["name"].startswith(PAIR_PREFIX):
            by_name.setdefault(ev["name"], []).append(ev)
    votes = Counter()
    placed = [a for a in annotations if a.name.startswith(PAIR_PREFIX)]
    for a in placed[:64]:
        for ev in by_name.get(a.name, ()):
            if abs((a.end - a.start) - ev["dur"] * 1e-6) < 0.1 * SAME_S:
                votes[round((a.start - ev["ts"] * 1e-6) / SAME_S)] += 1
    if not votes:
        return None
    return votes.most_common(1)[0][0] * SAME_S


def read(args: dict, obs):
    from neuronx_distributed_tpu import obs as program_obs

    tracer = program_obs.get_tracer()
    if (obs.trace is None or obs.reduction is None
            or not hasattr(tracer, "watch_host")):
        return None
    events = tracer.chrome_trace()["traceEvents"]
    shift = shift_of(obs.trace.annotations, events)
    if shift is None:
        return None
    lo, hi = obs.reduction.window
    ops = obs.trace.devices[min(obs.trace.devices)].ops
    idle = xplane.subtract(
        [(lo, hi)], xplane.clip([(e.start, e.end) for e in ops], lo, hi))
    if xplane.total(idle) <= 0:
        return None
    under = [(ev["ts"] * 1e-6 + shift, (ev["ts"] + ev["dur"]) * 1e-6 + shift)
             for ev in events if ev["name"] in args["spans"]]
    covered = xplane.total(idle) - xplane.total(xplane.subtract(idle, under))
    return 100.0 * covered / xplane.total(idle)
