"""Output tokens emitted inside the window per second of the window."""


def read(view):
    return view.win["tokens"] / view.seconds
