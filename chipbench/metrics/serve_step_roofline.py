"""Roofline share of the fused serve step, in % (shared by the serving
cells' ``serve_step_roofline.<cell>``): for the macro-steps of
the traced part of the window, the least time the chip could take (the
weights once per micro-step, each matrix product's at the width its
``matmul_precision`` reads it, the busy slots' embedding rows, each busy
slot's cached keys and values up to its position and the row it writes;
or the operations, if they take longer), over the device time of the
serve-step program."""
from readers import serve_step_roofline_percent


def read(w):
    return serve_step_roofline_percent(w)
