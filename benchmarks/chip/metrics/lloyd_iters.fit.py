"""Lloyd iterations the k-means backend ran in the traced fit
(``FitResult.counters["lloyd_iters"]``). Layer: backend."""
from chipbench import scopes


def read(ctx):
    return scopes.counter_total(ctx, "lloyd_iters")
