"""95th percentile of time to first token, in ms, over every request
due in the window: from when it was due (open loop) to when the host
received its first token, or to the window's close if it had none."""
from readers import p95


def read(w):
    return p95(1e3 * ((r["first"] if r["first"] is not None
                       and r["first"] <= w.t1 else w.t1) - r["due"])
               for r in w.records)
