"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the chips."""


def read(view):
    t = view["trace"]
    return 100 * (1 - t["busy_s"] / t["window_s"])
