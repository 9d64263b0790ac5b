"""Mean labeling phase seconds per build in the window, from
``BuildStats.label_seconds`` (host clock around blocking reads)."""


def read(layer):
    stats = layer.build_stats
    return sum(s.label_seconds for s in stats) / len(stats) if stats else None
