"""Host time per engine tick of the macro-steps that carried prompt
tokens (``serve.prefill_s`` over their ticks), in ms."""


def read(w):
    ticks = sum(s["ticks"] for s in w.steps if s["model"] and s["prefill"])
    return 1e3 * w.counters["serve.prefill_s"] / ticks if ticks else None
