"""Host time per round, in ms, of the ledger's block and its proof of
work (``bmoe.chain_s``)."""


def read(w):
    return 1e3 * w.counters["bmoe.chain_s"] / w.rounds if w.rounds else None
