"""Share (%) of the traced window in which no operation ran on the
device: 1 - (union of the device's operation intervals / window)."""


def read(view):
    tr = view.traced
    if not tr or not tr.trace.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
