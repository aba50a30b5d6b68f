"""Luby MIS rounds of the traced fit, summed over its ITIS levels
(``FitResult.counters["mis_rounds"]``, one count a level). Layer: ITIS
level."""
from chipbench import scopes


def read(ctx):
    return scopes.counter_total(ctx, "mis_rounds")
