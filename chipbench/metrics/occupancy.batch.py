"""Mean share of the batch slots holding a request, over the
macro-steps of the window (the engine's ``serve.occupancy``), in %."""


def read(w):
    total, count = w.occupancy
    return 100.0 * total / count if count else None
