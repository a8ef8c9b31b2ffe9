"""95th percentile, over requests that finished in the window, of the
time per output token after the first, in ms: (last token - first
token) / (tokens - 1), on the host clock."""
from readers import p95


def read(w):
    return p95(1e3 * (r["last"] - r["first"]) / (r["n"] - 1)
               for r in w.records
               if r["done"] and r["end"] <= w.t1 and r["n"] > 1)
