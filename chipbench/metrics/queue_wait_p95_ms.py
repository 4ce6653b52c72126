"""95th percentile, in ms, of the wait from due time to admission into
a slot, over the requests due in the window (admissions read from the
scheduler's ``TickInfo.admitted`` counts, in its FIFO order)."""
import numpy as np


def read(view):
    w = view.win["waits"]
    return 1e3 * float(np.percentile(w, 95)) if w else None
