"""Device seconds per build under the ``islabel.build.label`` spans: the
union of device-op intervals inside them, over the ``islabel.build``
spans in the trace."""
from harness import spans


def read(layer):
    return spans.device_seconds_per_build(layer.trace, "islabel.build.label")
