"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals / window).  Layer: device.  Moves
``tpot_p90_ms``."""


def read(r):
    return 100.0 * (1.0 - r.trace.busy_s() / r.trace.window_s)
