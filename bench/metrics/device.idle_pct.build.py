"""Share of the traced build window in which no operation ran on the
device: 1 - (union of device-op intervals) / window."""


def read(layer):
    return layer.trace.idle_pct()
