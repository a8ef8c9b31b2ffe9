"""Host time per engine tick of the macro-steps that only decoded
(``serve.decode_s`` over their ticks), in ms."""


def read(w):
    ticks = sum(s["ticks"] for s in w.steps
                if s["model"] and not s["prefill"])
    return 1e3 * w.counters["serve.decode_s"] / ticks if ticks else None
