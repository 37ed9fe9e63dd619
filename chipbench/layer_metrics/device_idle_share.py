"""Share of the traced window in which no operation ran on the chip."""


def read(view):
    if view.window is None:
        return None
    window_s = (view.window[1] - view.window[0]) / 1e9
    return 100.0 * (1.0 - view.busy_s() / window_s)
