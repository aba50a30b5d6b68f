"""Device seconds per fit in the prototype reduce (the sorted, banded
``segment_sum`` and its gathers): the ``itis.reduce`` scope of the ITIS
level programs (``_itis_step``). Layer: ITIS level."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_seconds_per_fit(ctx, "itis.reduce",
                                        scopes.LEVEL_PROGRAM)
