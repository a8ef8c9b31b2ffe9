"""Host time of the trust layer per engine tick, in ms: per-tick
Merkle commitments (``serve.commit_s``) and session audit drains
(``serve.audit_s``) over the ticks of the window."""


def read(w):
    ticks = sum(s["ticks"] for s in w.steps)
    if not ticks:
        return None
    return 1e3 * (w.counters["serve.commit_s"]
                  + w.counters["serve.audit_s"]) / ticks
