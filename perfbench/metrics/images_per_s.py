"""Images completed over the time they took: every request of the window
over the engine-clock span from the window's start to its last result."""


def read(r):
    n = len(r.window.done)
    return n / r.finished if n and r.finished > 0 else None
