"""Share (%) of the HBM roofline reached by the traced decode steps:
their useful bytes (every weight once, plus the key/value bytes of
each emitting row's valid context and its recurrent state, by
``counts.decode_bytes``) over the peak bandwidth, divided by their
device time. The step is memory-bound, so bytes set its roofline."""


def read(view):
    tr = view.traced
    s = tr.step_seconds("decode") if tr else None
    if not s:
        return None
    ctxs = tr.decode_contexts()
    useful = sum(view.counts.decode_bytes(view.m, c) for c in ctxs)
    return 100.0 * useful / view.peaks["hbm_bytes_per_s"] / sum(s)
