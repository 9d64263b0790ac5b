"""The control: the program with its own inexact path switched on.

The configurations state exactness. The step that would tempt a later
PR is to stop stage 2's relaxation early; the program has that path
itself, ``IndexConfig.max_relax_rounds`` (``QueryEngine.max_rounds``),
which caps the Bellman-Ford rounds. A correct comparison must find the
capped answers wrong.
"""
from __future__ import annotations


def capped_config(index_cfg: dict, rounds: int) -> dict:
    """A build configuration whose index stops stage 2 after ``rounds``."""
    return {**index_cfg, "max_relax_rounds": int(rounds)}
