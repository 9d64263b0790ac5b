"""Mean peel (hierarchy) phase seconds per build in the window, from
``BuildStats.peel_seconds`` (host clock around blocking reads)."""


def read(layer):
    stats = layer.build_stats
    return sum(s.peel_seconds for s in stats) / len(stats) if stats else None
