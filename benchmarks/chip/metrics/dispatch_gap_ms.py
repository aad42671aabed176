"""Mean device-idle time between two consecutive executions of the
chunk program (the driver's host sync at each chunk boundary), ms,
averaged over the chips."""
from devtrace import dispatch_gap_ms


def read(reading):
    return dispatch_gap_ms(reading.trace)
