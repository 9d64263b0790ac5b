"""Share of the label join's candidate slots that hold a real candidate:
100 · Σ ``BuildStats.label_candidates`` / Σ ``label_slots`` over the
builds in the window."""
from harness import spans


def read(layer):
    return spans.fill_pct(layer.build_stats, "label_candidates",
                          "label_slots")
