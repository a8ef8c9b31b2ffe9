"""95th percentile of queue wait, in ms: from when a request was due to
the start of the macro-step that admitted it (the window's close for
one not admitted yet), over every request due in the window."""
from readers import p95


def read(w):
    return p95(1e3 * ((r["admit"] if r["admit"] is not None else w.t1)
                      - r["due"]) for r in w.records)
