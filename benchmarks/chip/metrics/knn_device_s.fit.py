"""Device seconds per fit in the kNN graph: the ``tc.knn`` scope of the
ITIS level programs (``_itis_step``), the fused top-k kernel and what
feeds it. Layer: kernel, kNN graph."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_seconds_per_fit(ctx, "tc.knn", scopes.LEVEL_PROGRAM)
