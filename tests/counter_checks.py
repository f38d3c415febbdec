"""What a running engine's registry holds of the step's counters, against
what the family declares (``paging``, "A step's counters")."""

from neuronx_distributed_tpu.inference import paging


def declared_anywhere():
    """Every counter family a cache kind or a family's leaf can declare."""
    return {v.name: v for v in vars(paging).values()
            if isinstance(v, paging.CounterFamily)}


def check_registered_counters(registry, family):
    """Of the counters any family declares, ``registry`` holds those that
    ``family`` (a ``ServingFamily``) declares and no other family's: each
    with its help text and its ``kind`` children, in the declared order."""
    declared = {c.name: c for c in family.counters()}
    held = {name for name in declared_anywhere()
            if registry.get(name) is not None}
    assert held == set(declared)
    for name, c in declared.items():
        metric = registry.get(name)
        assert metric.help == c.help
        assert [child.labels.get("kind") for child in metric.children()] \
            == (list(c.kinds) or [None])
