"""Median, in ms, of every gap between consecutive tokens of every
request, both tokens inside the window: the pace a reader sees while
the scheduler mixes prompt steps into every tick."""
import numpy as np


def read(view):
    g = view.win["gaps"]
    return 1e3 * float(np.percentile(g, 50)) if g else None
