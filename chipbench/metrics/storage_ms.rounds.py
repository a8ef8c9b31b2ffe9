"""Host time per round, in ms, of the storage layer (bank resolution
through the edge cache, expert versions published) (``bmoe.storage_s``)."""


def read(w):
    return 1e3 * w.counters["bmoe.storage_s"] / w.rounds if w.rounds else None
