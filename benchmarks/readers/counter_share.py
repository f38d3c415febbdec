"""Share of a labelled counter of the program under test, in percent:
``{"counter": name, "label": label, "numerator": [values]}`` is the sum of
the counter's children whose ``label`` is one of ``numerator`` over the
sum of all its children, read from the process's ``obs.get_registry()``
when the run has ended. A share and never a count: the registry is reset
at the window's start and counts on through the traced seconds of the
same load."""


def read(args: dict, obs):
    from neuronx_distributed_tpu import obs as program_obs

    counter = program_obs.get_registry().get(args["counter"])
    if counter is None:
        return None
    children = counter.children()
    total = sum(c.value for c in children)
    if total <= 0:
        return None
    hit = sum(c.value for c in children
              if c.labels.get(args["label"]) in args["numerator"])
    return 100.0 * hit / total
