"""Generated tokens the host received in the window, per second of
the window."""


def read(w):
    return w.delivered / w.seconds
