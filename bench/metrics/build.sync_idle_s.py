"""Device-idle seconds per build under the ``islabel.sync`` spans: the
host stalls of the build's counted blocking reads (``host_read``)."""
from harness import spans


def read(layer):
    return spans.idle_seconds_per_build(layer.trace, spans.SYNC)
