"""Share (%) of the traced window in which no operation ran on the device:
1 - the union of the trace's device-op intervals over the window span."""


def read(record):
    tr = record["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
