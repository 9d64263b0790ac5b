"""Mean assemble phase seconds per build in the window, from
``BuildStats.assemble_seconds`` (the ``islabel.build.assemble`` span:
the label copy to the host and the core's upload)."""
from harness import spans


def read(layer):
    total = spans.stats_sum(layer.build_stats, "assemble_seconds")
    return None if total is None else total / len(layer.build_stats)
