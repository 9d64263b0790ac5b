"""Share of the peel's augmentation-buffer rows that hold an IS-incident
edge: 100 · Σ ``BuildStats.peel_aug_edges`` / Σ ``peel_aug_slots`` over
the builds in the window."""
from harness import spans


def read(layer):
    return spans.fill_pct(layer.build_stats, "peel_aug_edges",
                          "peel_aug_slots")
