"""Host time per round, in ms, of the jitted round step (dispatch, experts,
combine, update) (``bmoe.compute_s``)."""


def read(w):
    return 1e3 * w.counters["bmoe.compute_s"] / w.rounds if w.rounds else None
