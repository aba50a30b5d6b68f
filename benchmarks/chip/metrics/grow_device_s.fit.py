"""Device seconds per fit growing TC's clusters from the seeds (leftover
assignment and compaction): the ``tc.grow`` scope of the ITIS level
programs (``_itis_step``). Layer: ITIS level."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_seconds_per_fit(ctx, "tc.grow", scopes.LEVEL_PROGRAM)
