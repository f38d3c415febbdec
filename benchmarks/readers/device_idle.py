"""Share of the traced window in which no operation ran on the device
(mean over the chips used), in percent."""


def read(args: dict, obs):
    red = obs.reduction
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
