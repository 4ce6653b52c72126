"""Seconds from process start to the window's start: imports, weights,
engine and scheduler, loading or compiling the programs, warm-up and
pre-roll."""


def read(view):
    return view.setup_s
