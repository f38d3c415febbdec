"""Model FLOP/s utilisation: operations the forward and backward passes
need per token (``work.<function>``, recomputation not counted) times the
tokens per second of the window, over chips times the bf16 peak."""

from readers import work


def read(args: dict, obs):
    rate = obs.end_to_end.get(args["rate"])
    if rate is None or obs.peaks is None:
        return None
    per_token = getattr(work, args["work"])(obs)
    return 100.0 * per_token * rate / (
        obs.chips * obs.peaks["bf16_flops_per_s"])
