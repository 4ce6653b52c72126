"""Mean device time, in ms, of one decode step program in the traced
window."""


def read(view):
    s = view.traced.step_seconds("decode") if view.traced else None
    return 1e3 * sum(s) / len(s) if s else None
