"""Device seconds per fit in the Luby MIS rounds that pick TC's seeds: the
``tc.mis`` scope of the ITIS level programs (``_itis_step``). Layer: ITIS
level."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_seconds_per_fit(ctx, "tc.mis", scopes.LEVEL_PROGRAM)
