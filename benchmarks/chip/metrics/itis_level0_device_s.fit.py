"""Device seconds per fit of ITIS level 0: the first ``_itis_step``
execution inside each ``bench.fit`` span, its leaf operations summed. A
fit must run as many level programs as it has level maps, or nothing is
read. Layer: ITIS level 0."""
from chipbench import scopes


def read(ctx):
    if ctx.loop != "fit" or ctx.trace is None or not ctx.level_valid:
        return None
    levels = scopes.level_seconds(ctx.trace, levels=len(ctx.level_valid))
    return levels[0] if levels and levels[0] > 0 else None
