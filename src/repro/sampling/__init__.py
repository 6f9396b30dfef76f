"""Weighted random sampling substrate (Hübschle-Schneider & Sanders,
"Parallel Weighted Random Sampling").

Alias tables give O(1) weighted draws after O(n) construction; the ADS
instance built on top estimates a weighted mean adaptively (stop on relative
standard error — :class:`~repro.core.stopping.RelativeErrorCondition`).
"""
from .alias import (AliasTable, alias_draw, alias_draw_probabilities,
                    build_alias_table, make_weighted_sample_fn,
                    weighted_frame_template, weighted_mean_exact)

__all__ = [
    "AliasTable", "build_alias_table", "alias_draw", "alias_draw_probabilities",
    "make_weighted_sample_fn", "weighted_frame_template",
    "weighted_mean_exact",
]
