"""Share of the traced window in which no operation ran on the
device, in % (shared by every cell's ``device_idle.<cell>``)."""
from readers import device_idle_percent


def read(w):
    return device_idle_percent(w)
