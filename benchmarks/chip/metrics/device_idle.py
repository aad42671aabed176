"""Share of the traced window in which no operation ran on the device,
percent, averaged over the chips."""
from devtrace import device_idle_pct


def read(reading):
    return device_idle_pct(reading.trace)
