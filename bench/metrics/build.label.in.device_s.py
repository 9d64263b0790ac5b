"""Device seconds per build under the ``islabel.build.label.in`` spans
(the in-label family of a directed build): the union of device-op
intervals inside them, over the ``islabel.build`` spans in the trace.
None where the program opens no such span."""
from harness import spans


def read(layer):
    return spans.device_seconds_per_build(layer.trace,
                                          "islabel.build.label.in")
