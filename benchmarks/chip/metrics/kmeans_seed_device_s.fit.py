"""Device seconds per fit in k-means++ seeding: the ``kmeans.seed`` scope
of the k-means backend program (``_kmeans``). Layer: backend."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_seconds_per_fit(ctx, "kmeans.seed",
                                        scopes.BACKEND_PROGRAM)
