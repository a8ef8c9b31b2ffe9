"""Model FLOP/s utilization of a served model, in % (shared by the
serving cells' ``mfu.<cell>``): the forward operations required by
every prompt and generated token the window processed (each at its
position, attention included), over the window and the chip's bf16
peak."""
from readers import mfu_percent


def read(w):
    return mfu_percent(w)
