"""Host time per round, in ms, of the trust layer: commitment, audits
(drained off the round's path), court and rollback (``bmoe.consensus_s``
+ ``bmoe.audit_s``)."""


def read(w):
    if not w.rounds:
        return None
    return 1e3 * (w.counters["bmoe.consensus_s"]
                  + w.counters["bmoe.audit_s"]) / w.rounds
