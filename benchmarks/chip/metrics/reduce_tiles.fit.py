"""Tile matmuls the prototype reduce's ``segment_sum`` ran in the traced
fit, summed over its ITIS levels (``FitResult.counters["reduce_tiles"]``,
one count a level; 0, so nothing read, off the Pallas path). Layer: ITIS
level."""
from chipbench import scopes


def read(ctx):
    return scopes.counter_total(ctx, "reduce_tiles")
