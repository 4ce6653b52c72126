"""Model operations of the traced window (every emitted token's step,
attention over its valid context included, plus each prompt whose
first token came in the window) over the window's length times the
chip's bf16 peak, in %."""


def read(view):
    tr = view.traced
    if not tr or not tr.trace.ops or tr.window_s <= 0:
        return None
    f = tr.flops(view.m)
    if f == 0:
        return None
    return 100.0 * f / (tr.window_s * view.peaks["bf16_flops"])
