"""Median time to first token, in ms, of every request due in the
window, from its due time; one with no first token by the window's end
counts at the end."""
import numpy as np


def read(view):
    t = view.win["ttft"]
    return 1e3 * float(np.percentile(t, 50)) if t else None
