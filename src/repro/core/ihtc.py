"""IHTC — Iterative Hybridized Threshold Clustering (the paper's §3.2).

ITIS reduces n units to ≤ n/(t*)^m weighted prototypes, a "sophisticated"
clusterer (k-means / HAC / DBSCAN / any callable) runs on the prototypes,
and labels are backed out to all n units. Guarantee: every final cluster
contains ≥ (t*)^m original units.

Since the planner/executor split (DESIGN.md §13) this module owns exactly
one thing: the **memory executor** — the data-movement strategy for a
dataset resident on one device (the per-level ``itis_step`` loop over
static buffers). Validation, level scheduling, backend finalize and label
back-out live once in :mod:`repro.core.plan`; :func:`ihtc` survives as a
thin deprecation alias over ``repro.fit``.
"""
from __future__ import annotations

from typing import Optional, Union

import jax

from repro.cluster.registry import BackendFn, resolve_backend
from repro.core.itis import itis
from repro.core.plan import FitPlan, FitResult, Reduction, fit, register_executor

# backwards-compatible aliases: backend resolution lives in the registry,
# the result type in the planner
_resolve_backend = resolve_backend
IHTCResult = FitResult


@register_executor("memory")
def _execute_memory(plan: FitPlan, x: jax.Array) -> Reduction:
    """Single-device resident-array strategy: every level is one jitted
    ``itis_step`` over a static padded buffer; assignment maps stay on
    device for the planner's back-out."""
    key_itis, _ = plan.split_keys()
    r = itis(
        x, plan.t, plan.m, weights=plan.weights, key=key_itis,
        weighted=plan.weighted, impl=plan.impl, knn_block=plan.knn_block,
        min_points=plan.min_points, n_blocks=plan.n_blocks,
    )
    return Reduction(
        protos=r.protos, mass=r.mass, valid=r.valid,
        n_prototypes=r.n_prototypes, assignments=r.assignments,
        n0=x.shape[0], counters={"mis_rounds": tuple(r.mis_rounds),
                                 "reduce_tiles": tuple(r.reduce_tiles)},
    )


def ihtc(
    x: jax.Array,
    t: int,
    m: int,
    backend: Union[str, BackendFn] = "kmeans",
    *,
    weights: Optional[jax.Array] = None,
    weighted: bool = False,
    use_mass_in_backend: bool = True,
    key: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    knn_block: Optional[int] = None,
    mesh=None,
    axis_name: Optional[str] = None,
    **backend_kwargs,
) -> FitResult:
    """Full IHTC pipeline on a resident array (deprecated alias of
    :func:`repro.fit` — prefer that entry point for new code).

    ``weighted`` controls ITIS centroid weighting (paper-faithful default:
    False). ``use_mass_in_backend`` feeds prototype masses as sample weights
    to the backend clusterer (paper runs backends unweighted; mass-weighting
    is the statistically consistent variant — both supported).

    ``backend`` is a registered name (:mod:`repro.cluster.registry`) or any
    callable satisfying the BackendFn contract. ``impl``/``knn_block``/
    ``mesh``/``axis_name`` default to the active runtime config, so
    ``with runtime.configure(mesh=...)`` shards this call without touching
    the call site.

    Passing ``mesh`` (or configuring one) plans the "sharded" executor:
    every level is sharded over the mesh's ``axis_name`` axis and the
    points are never gathered to one device (DESIGN.md §4 has the
    determinism contract between the two paths). An explicit ``knn_block``
    is rejected there — the ring kNN has no blocked scan to apply it to.
    """
    return fit(
        x, t, m, backend,
        weights=weights, weighted=weighted,
        use_mass_in_backend=use_mass_in_backend, key=key, impl=impl,
        knn_block=knn_block, mesh=mesh, axis_name=axis_name, driver="ihtc",
        **backend_kwargs,
    )
