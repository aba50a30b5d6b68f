"""Unified fit planner/executor architecture — one ``repro.fit()`` over
every execution strategy.

The paper's pipeline is one algorithm — ITIS reduces n units to weighted
prototypes, a registered backend labels the prototypes, labels are backed
out — but the repo had grown three hand-rolled drivers (``ihtc``,
``ihtc_sharded``, ``ihtc_streaming``) that each re-implemented parameter
validation, level scheduling, backend finalize and label back-out, returned
three result types, and could not compose (no out-of-core *and*
multi-device fit). This module is the split that makes the aggregation
layer a pluggable front-end instead of three incidental copies:

  * :class:`FitPlan` — everything decided *before* any data moves: the
    reduction parameters (validated once), the key schedule, the backend
    spec, every dispatch knob resolved from the active
    :class:`repro.runtime.RuntimeConfig`, and the **executor** choice
    (``chunk stream → streaming``, ``mesh → sharded``, both → the composed
    ``streaming_sharded`` path).
  * the **executor registry** — ``@register_executor("memory")`` etc.; an
    executor owns exactly one thing, its data-movement strategy, and
    returns a :class:`Reduction` (final prototype buffers + the back-out
    maps it spilled along the way).
  * the **planner epilogue** — backend finalize (registry resolution,
    mass-weighting, ``-1`` masking of invalid rows) and label back-out
    (:func:`repro.core.prototypes.compose_assignments` on device maps, or
    host composition over a :class:`LabelSpill`) live here exactly once.
  * :class:`FitResult` — the one canonical fitted artifact every executor
    returns (a superset of the old ``IHTCResult`` / ``StreamingIHTCResult``,
    which survive as thin deprecation aliases).

``repro.fit(x_or_chunks, t, m, backend)`` is the public entry point;
``ClusterIndex.build`` and ``ClusterService.from_fit`` consume the result
uniformly. DESIGN.md §13 documents the executor contract and the
composed-reservoir invariants.
"""
from __future__ import annotations

import dataclasses
from typing import (
    Any, Callable, Dict, Iterator, List, Mapping, NamedTuple, Optional,
    Sequence, Tuple, Union,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.cluster.registry import BackendFn, resolve_backend
from repro.core.itis import level_sizes, validate_reduction_params
from repro.core.prototypes import compose_assignments

# ---------------------------------------------------------------------------
# executor registry (the twin of repro.cluster.registry, one level up:
# backends label prototypes, executors move data)
# ---------------------------------------------------------------------------

# uniform executor signature: reduction = fn(plan, data)
ExecutorFn = Callable[["FitPlan", Any], "Reduction"]

#: executors that place level buffers on a mesh (and therefore must not be
#: handed a single-device ``knn_block`` — see :func:`plan_fit`)
SHARDED_EXECUTORS = ("sharded", "streaming_sharded")

#: executors that consume a chunk iterator instead of a resident array
STREAMING_EXECUTORS = ("streaming", "streaming_sharded")

_REGISTRY: Dict[str, ExecutorFn] = {}


def register_executor(name: str) -> Callable[[ExecutorFn], ExecutorFn]:
    """Decorator: ``@register_executor("memory")`` on an ExecutorFn."""

    def deco(fn: ExecutorFn) -> ExecutorFn:
        if name in _REGISTRY and _REGISTRY[name] is not fn:
            raise ValueError(f"executor {name!r} is already registered "
                             f"({_REGISTRY[name]!r})")
        _REGISTRY[name] = fn
        return fn

    return deco


def _ensure_builtin_executors() -> None:
    # importing the modules runs their @register_executor decorators; local
    # import keeps plan importable from anywhere without a cycle
    from repro.core import distributed, ihtc, streaming  # noqa: F401


def resolve_executor(name: str) -> ExecutorFn:
    """Executor name → registered ExecutorFn (the one resolution point)."""
    _ensure_builtin_executors()
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown executor {name!r}; have {available_executors()}")
    return _REGISTRY[name]


def available_executors() -> list:
    """Sorted names of every registered executor."""
    _ensure_builtin_executors()
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# the executor output contract
# ---------------------------------------------------------------------------


class LabelSpill:
    """Host-side back-out state a streaming executor spilled while it ran.

    One int32 assignment map per chunk (chunk-local prototype id, ``-1`` for
    masked rows) plus one map per cascade / compaction / finalize level, in
    epoch order; ``chunk_offset`` places each chunk's prototype slab in the
    reservoir and ``chunk_epoch`` says how many maps existed at fold time,
    so a chunk is only composed through the maps recorded at-or-after its
    fold (DESIGN.md §12). Everything here is host numpy — nothing O(n) ever
    lands on device: the constructor enforces the forced-copy contract
    (every map a real ``np.ndarray``), so a deferred spill drain that
    forgot to materialize a device buffer fails here, not at back-out.

    ``ingest_stats`` (optional) carries the stream loop's pipeline
    telemetry (DESIGN.md §18): prefetch depth, donation flag, loop wall
    seconds and the time the consumer spent waiting on ingest —
    benchmarks/bench_ingest.py derives ``device_idle_frac`` from it.
    """

    def __init__(
        self,
        *,
        chunk_n: int,
        chunk_assign: List[np.ndarray],
        chunk_offset: List[int],
        chunk_epoch: List[int],
        chunk_counts: List[int],
        maps: List[np.ndarray],
        n_cascades: int,
        ingest_stats: Optional[dict] = None,
    ):
        for name, arrs in (("chunk_assign", chunk_assign), ("maps", maps)):
            for a in arrs:
                if not isinstance(a, np.ndarray):
                    raise TypeError(
                        f"LabelSpill.{name} must be host numpy (forced "
                        f"copies, §12); got {type(a).__name__} — a spill "
                        f"drain left a device buffer behind")
        self.chunk_n = chunk_n
        self.chunk_assign = chunk_assign
        self.chunk_offset = chunk_offset
        self.chunk_epoch = chunk_epoch
        self.chunk_counts = chunk_counts
        self.maps = maps
        self.n_cascades = n_cascades
        self.ingest_stats = ingest_stats

    @property
    def n_chunks(self) -> int:
        return len(self.chunk_assign)

    @property
    def n_total(self) -> int:
        return int(sum(self.chunk_counts))

    def labels_for(self, chunk_idx: int,
                   proto_labels_host: np.ndarray) -> np.ndarray:
        """Compose chunk ``chunk_idx``'s map through every level map from
        its epoch onward, then through the backend labels (pure numpy)."""
        count = self.chunk_counts[chunk_idx]
        lab = self.chunk_assign[chunk_idx][:count].astype(np.int64)
        slot = np.where(lab >= 0, lab + self.chunk_offset[chunk_idx], -1)
        for mp in self.maps[self.chunk_epoch[chunk_idx]:]:
            slot = np.where(slot >= 0, mp[np.maximum(slot, 0)], -1)
        out = np.where(slot >= 0, proto_labels_host[np.maximum(slot, 0)], -1)
        return out.astype(np.int32)


class Reduction(NamedTuple):
    """What an executor hands back to the planner: the final prototype
    buffers plus whatever back-out state its data-movement strategy
    produced (device-resident level maps, or a host :class:`LabelSpill`).
    The planner owns everything after this point — backend finalize and
    label back-out — so no executor ever touches the backend registry."""

    protos: jax.Array          # (n_max, d) final-level prototypes (padded)
    mass: jax.Array            # (n_max,)
    valid: jax.Array           # (n_max,) bool
    n_prototypes: jax.Array    # () int32 — valid count at the final level
    assignments: Sequence[jax.Array]  # device level maps ([] for streaming)
    n0: int                    # original unit count (back-out slice length)
    spill: Optional[LabelSpill] = None
    counters: Optional[Dict[str, Any]] = None  # see FitResult.counters


# ---------------------------------------------------------------------------
# the canonical result
# ---------------------------------------------------------------------------


class _SpillLabels:
    """Lazy label view over a :class:`LabelSpill`.

    Kept callable so the historical streaming API ``result.labels()`` keeps
    working, and array-convertible (``np.asarray(result.labels)``) so the
    in-memory idiom works on streamed fits too. Prefer
    :meth:`FitResult.iter_labels` at scale — this view concatenates."""

    def __init__(self, result: "FitResult"):
        self._result = result

    def __call__(self) -> np.ndarray:
        r = self._result
        if r.n_chunks == 0:
            return np.zeros((0,), np.int32)
        return np.concatenate(list(r.iter_labels()))

    def __array__(self, dtype=None):
        out = self()
        return out if dtype is None else out.astype(dtype)

    def __repr__(self) -> str:
        return (f"<spilled labels of {self._result.n_total} units over "
                f"{self._result.n_chunks} chunks; call or np.asarray() to "
                f"materialize>")


class FitResult:
    """Canonical fitted artifact of every executor (DESIGN.md §13).

    Device-resident (all O(n/(t*)^m) or O(reservoir), never O(n) for the
    streaming family): ``protos`` / ``proto_mass`` / ``proto_valid`` — the
    final prototype buffer; ``proto_labels`` — backend labels (-1 for
    padding/noise); ``n_prototypes`` — valid count.

    ``labels``: for in-memory executors, the (n,) int32 device array backed
    out through the level maps; for streaming executors, a lazy host view
    (callable, the historical API, and ``np.asarray``-able) composed from
    the :class:`LabelSpill`. ``labels_for(i)`` / ``iter_labels()`` stream
    labels chunk-by-chunk for either family; ``to_index()`` freezes the
    servable :class:`repro.core.index.ClusterIndex`.

    ``counters``: device int32 counts the fit's programs returned, read
    without a host sync (DESIGN.md §20): ``mis_rounds`` (memory executor,
    a tuple of () scalars, the Luby rounds of each level run;
    ``np.asarray`` stacks them), ``reduce_tiles`` (memory executor, the
    same shape: each level's prototype reduce, its (segment tile, row
    tile) matmuls summed over the blocks, 0 on the reference path) and
    ``lloyd_iters`` (k-means backend, a () scalar). Other executors and
    backends leave theirs out.

    The old ``IHTCResult`` / ``StreamingIHTCResult`` names are deprecation
    aliases of this class.
    """

    def __init__(
        self,
        *,
        executor: str,
        protos: jax.Array,
        proto_mass: jax.Array,
        proto_valid: jax.Array,
        proto_labels: jax.Array,
        n_prototypes: jax.Array,
        assignments: Sequence[jax.Array] = (),
        labels: Optional[jax.Array] = None,
        spill: Optional[LabelSpill] = None,
        counters: Optional[Dict[str, Any]] = None,
    ):
        if (labels is None) == (spill is None):
            raise ValueError("FitResult needs exactly one of labels= "
                             "(in-memory back-out) or spill= (streaming)")
        self.executor = executor
        self.protos = protos
        self.proto_mass = proto_mass
        self.proto_valid = proto_valid
        self.proto_labels = proto_labels
        self.n_prototypes = n_prototypes
        self.assignments = assignments
        self.spill = spill
        self.counters = dict(counters or {})
        self._labels = labels
        self._proto_labels_host: Optional[np.ndarray] = None

    # ---- labels -----------------------------------------------------------

    @property
    def labels(self):
        """(n,) int32 device labels (in-memory executors) or the lazy host
        view over the spill (streaming executors; call it or np.asarray)."""
        if self._labels is not None:
            return self._labels
        return _SpillLabels(self)

    def _proto_labels_np(self) -> np.ndarray:
        if self._proto_labels_host is None:
            # repro: allow[HS201]: fit epilogue — final labels materialize to host once, cached; the fit loop is already complete
            self._proto_labels_host = np.asarray(self.proto_labels)
        return self._proto_labels_host

    def labels_for(self, chunk_idx: int) -> np.ndarray:
        """Final labels of chunk ``chunk_idx``'s valid rows (host numpy).
        In-memory fits are one chunk: only index 0 exists."""
        if self.spill is not None:
            return self.spill.labels_for(chunk_idx, self._proto_labels_np())
        if chunk_idx != 0:
            raise IndexError(
                f"in-memory fit has a single chunk; got index {chunk_idx}")
        # repro: allow[HS201]: fit epilogue — labels_for is the documented host hand-off point, after the fit completed
        return np.asarray(self._labels)

    def iter_labels(self) -> Iterator[np.ndarray]:
        """Final labels, one array per input chunk, in stream order."""
        for c in range(self.n_chunks):
            yield self.labels_for(c)

    # ---- stream bookkeeping (degenerate for in-memory fits) ---------------

    @property
    def n_chunks(self) -> int:
        return self.spill.n_chunks if self.spill is not None else 1

    @property
    def n_total(self) -> int:
        if self.spill is not None:
            return self.spill.n_total
        return int(self._labels.shape[0])

    @property
    def n_cascades(self) -> int:
        return self.spill.n_cascades if self.spill is not None else 0

    @property
    def chunk_n(self) -> Optional[int]:
        return self.spill.chunk_n if self.spill is not None else None

    # ---- conversion -------------------------------------------------------

    def to_index(self, *, pack: bool = True):
        """Freeze into a servable :class:`repro.core.index.ClusterIndex`
        (via :meth:`ClusterIndex.build` — ``pack=True`` also freezes the
        bf16/int8 prototype buffers the quantized assign variants serve
        from; bitwise-identical assigns either way, the packed form just
        skips the per-trace repack)."""
        from repro.core.index import ClusterIndex  # lazy: no import cycle

        return ClusterIndex.build(self, pack=pack)

    def __repr__(self) -> str:
        return (f"FitResult(executor={self.executor!r}, "
                f"n_prototypes={int(self.n_prototypes)}, "
                f"n_chunks={self.n_chunks})")


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class FitPlan:
    """Everything decided before any data moves.

    Reduction parameters are validated at construction; the key schedule,
    level schedule and shard-padding rules live here as methods so no
    executor re-implements them. Executors read the plan; only the planner
    (:func:`execute_plan`) runs the backend and backs labels out.
    """

    t: int
    m: int
    backend: Union[str, BackendFn]
    executor: str
    key: jax.Array
    weighted: bool = False
    use_mass_in_backend: bool = True
    impl: str = "auto"
    knn_block: int = 0
    block_q: int = 256
    block_k: int = 512
    n_blocks: int = 8
    chunk_n: int = 0
    reservoir_n: int = 0
    prefetch_depth: int = 0
    donate_stream: bool = False
    mesh: Any = None
    axis_name: str = "data"
    min_points: int = 4
    weights: Optional[jax.Array] = None
    valid: Optional[jax.Array] = None
    driver: str = "fit"
    backend_kwargs: Mapping[str, Any] = dataclasses.field(
        default_factory=dict)

    # ---- the logic the three drivers used to re-implement -----------------

    def schedule(self, n0: int, *, multiple: int = 1) -> List[int]:
        """Static buffer size of every level, 0..m inclusive (the single
        source both single- and multi-device executors derive shapes from).
        """
        return level_sizes(n0, self.t, self.m, multiple=multiple)

    def reduction_floor(self) -> int:
        """Fewer valid points than this and a level must not run (the
        shared early-stop rule: reduction would collapse everything)."""
        return max(self.min_points, 2 * self.t)

    def shard_count(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.axis_name]

    def shard_multiple(self) -> int:
        """Level-buffer padding multiple for mesh executors: the smallest
        multiple of the shard count covering the canonical reduction block
        width, so every level splits evenly and the fixed-tree segment sums
        stay bit-comparable to the single-device path (DESIGN.md §4.3)."""
        p = self.shard_count()
        return -(-max(self.n_blocks, p) // p) * p

    def split_keys(self) -> Tuple[jax.Array, jax.Array]:
        """(key_itis, key_backend) — the root split every executor shares,
        so aligned configs reproduce each other bit-for-bit."""
        key_itis, key_backend = jax.random.split(self.key)
        return key_itis, key_backend


def _is_chunk_stream(data: Any) -> bool:
    """Resident 2-D array → in-memory family; any other iterable → chunks."""
    return not (hasattr(data, "ndim") and hasattr(data, "shape"))


def plan_fit(
    data: Any,
    t: int,
    m: int,
    backend: Union[str, BackendFn] = "kmeans",
    *,
    executor: Optional[str] = None,
    weights: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None,
    weighted: bool = False,
    use_mass_in_backend: bool = True,
    key: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    knn_block: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    n_blocks: Optional[int] = None,
    chunk_n: Optional[int] = None,
    reservoir_n: Optional[int] = None,
    prefetch_depth: Optional[int] = None,
    donate_stream: Optional[bool] = None,
    mesh=None,
    axis_name: Optional[str] = None,
    min_points: int = 4,
    driver: str = "fit",
    **backend_kwargs,
) -> FitPlan:
    """Resolve one :class:`FitPlan` from the call, the input shape and the
    active runtime config (explicit kwargs win — the §10 contract).

    Executor choice (when neither ``executor=`` nor the config names one):
    a chunk iterator streams, a resident array stays in memory, and a mesh
    (explicit or configured) upgrades either to its sharded flavour —
    ``streaming + mesh`` is the composed out-of-core multi-device path.

    Inputs the chosen executor cannot honour are rejected loudly rather
    than silently dropped: ``knn_block`` on sharded executors (the ring
    kNN has no blocked scan), ``weights`` on streaming executors (chunk
    streams carry unit mass), and ``valid`` anywhere but the ``sharded``
    executor (streams mask rows with ``(chunk, n_valid)`` pairs instead).
    """
    cfg = runtime.active()
    explicit_knn_block = knn_block is not None
    auto_block_q = block_q is None
    auto_block_k = block_k is None
    impl = cfg.impl if impl is None else impl
    knn_block = cfg.knn_block if knn_block is None else knn_block
    block_q = cfg.block_q if block_q is None else block_q
    block_k = cfg.block_k if block_k is None else block_k
    n_blocks = cfg.n_blocks if n_blocks is None else n_blocks
    chunk_n = cfg.chunk_n if chunk_n is None else chunk_n
    reservoir_n = cfg.reservoir_n if reservoir_n is None else reservoir_n
    explicit_prefetch = prefetch_depth is not None
    explicit_donate = donate_stream is not None
    prefetch_depth = (cfg.prefetch_depth if prefetch_depth is None
                      else prefetch_depth)
    donate_stream = (cfg.donate_stream if donate_stream is None
                     else donate_stream)
    mesh = cfg.mesh if mesh is None else mesh
    axis_name = cfg.axis_name if axis_name is None else axis_name
    if key is None:
        key = jax.random.PRNGKey(0)

    streaming_input = _is_chunk_stream(data)
    if executor is None and cfg.executor != "auto":
        executor = cfg.executor
    if executor is None:
        if streaming_input:
            executor = "streaming_sharded" if mesh is not None else "streaming"
        else:
            executor = "sharded" if mesh is not None else "memory"
    resolve_executor(executor)  # unknown names fail here, loudly

    if streaming_input and executor not in STREAMING_EXECUTORS:
        raise ValueError(
            f"{driver}: executor {executor!r} needs a resident (n, d) array "
            f"but got a chunk stream; use a streaming executor or pass the "
            f"materialized array")
    if not streaming_input and executor in STREAMING_EXECUTORS:
        raise ValueError(
            f"{driver}: executor {executor!r} consumes an iterable of host "
            f"chunks; wrap a resident array as iter([x]) to stream it")
    if executor in SHARDED_EXECUTORS and mesh is None:
        from repro.core.distributed import make_data_mesh  # lazy: no cycle

        mesh = make_data_mesh()

    # satellite fix: ihtc() used to silently DROP knn_block when a mesh
    # dispatched it to the sharded path (ring_knn shards keys instead of
    # blocking them, so the knob cannot be honoured there). Reject loudly.
    if executor in SHARDED_EXECUTORS and explicit_knn_block and knn_block:
        raise ValueError(
            f"{driver}: knn_block={knn_block} cannot apply to the "
            f"{executor!r} executor — the sharded kNN is a ring pass over "
            f"mesh shards (repro.core.knn.ring_knn), not a blocked scan; "
            f"drop the kwarg (a configured runtime knn_block is ignored on "
            f"sharded executors) or run a single-device executor")

    # same loud-reject treatment for inputs an executor cannot honour:
    # silently dropping a weight vector or a validity mask would corrupt
    # the fit in ways that only surface at scale
    if weights is not None and executor in STREAMING_EXECUTORS:
        raise ValueError(
            f"{driver}: weights= cannot apply to the {executor!r} executor "
            f"— per-unit weights need the resident array; chunk streams "
            f"carry unit mass (fold weighted data into the chunks, or use "
            f"an in-memory executor)")
    if valid is not None and executor != "sharded":
        raise ValueError(
            f"{driver}: valid= marks pre-padded rows of a resident mesh "
            f"array and only the 'sharded' executor honours it (got "
            f"{executor!r}); slice the array instead, or mask stream "
            f"chunks with (chunk, n_valid) pairs")
    if prefetch_depth < 0:
        raise ValueError(
            f"{driver}: prefetch_depth must be >= 0, got {prefetch_depth}")
    # the ingest-pipeline knobs only mean something to the stream loop
    # (DESIGN.md §18); an explicit value on an in-memory executor would be
    # silently dropped, so reject it like knn_block/weights above
    if executor not in STREAMING_EXECUTORS:
        if explicit_prefetch and prefetch_depth:
            raise ValueError(
                f"{driver}: prefetch_depth={prefetch_depth} cannot apply "
                f"to the {executor!r} executor — only the streaming "
                f"executors stage chunks (a configured runtime "
                f"prefetch_depth is ignored elsewhere)")
        if explicit_donate and donate_stream:
            raise ValueError(
                f"{driver}: donate_stream=True cannot apply to the "
                f"{executor!r} executor — only the streaming executors "
                f"hold a reservoir to donate (a configured runtime "
                f"donate_stream is ignored elsewhere)")

    # tuned-dispatch resolution (DESIGN.md §14): with the tuning policy
    # active, auto knobs resolve through the measured winners for this
    # hardware + shape bucket and the results are FROZEN into the plan, so
    # executor dispatch stays deterministic for the plan's lifetime even if
    # the cache mutates mid-fit. Explicit kwargs and non-auto configured
    # values still win; tune="off" leaves every constant bit-for-bit.
    if cfg.tune != "off":
        from repro import tune  # lazy: no cycle through core

        if streaming_input:
            if chunk_n == 0 or (prefetch_depth == 0
                                and not explicit_prefetch):
                ts = tune.tuned_params("stream")
                if chunk_n == 0:
                    if ts.get("chunk_n"):
                        chunk_n = int(ts["chunk_n"])
                    if reservoir_n == 0 and ts.get("reservoir_n"):
                        reservoir_n = int(ts["reservoir_n"])
                # depth 0 is the serial default, not a measured choice:
                # treat it as "auto" unless the caller pinned it (explicit
                # kwargs always win; donation stays manual — it changes
                # buffer lifetimes, not a measurable constant)
                if (prefetch_depth == 0 and not explicit_prefetch
                        and ts.get("prefetch_depth") is not None):
                    prefetch_depth = int(ts["prefetch_depth"])
        else:
            n0, d0 = int(data.shape[0]), int(data.shape[1])
            dt = str(data.dtype) if hasattr(data, "dtype") else "float32"
            tk = tune.tuned_params("knn", dtype=dt, n=n0, d=d0,
                                   k=max(t - 1, 1))
            if auto_block_q and tk.get("block_q"):
                block_q = int(tk["block_q"])
            if auto_block_k and tk.get("block_k"):
                block_k = int(tk["block_k"])
            if (knn_block == 0 and not explicit_knn_block
                    and executor not in SHARDED_EXECUTORS):
                tb = tune.tuned_params("knn_block", dtype=dt, n=n0, d=d0,
                                       k=max(t - 1, 1))
                if tb.get("knn_block"):
                    knn_block = int(tb["knn_block"])
            # the "assign" cell tunes the fused nearest/top-k family
            # (DESIGN.md §16): if its measured winner is a fused variant
            # and the impl policy is auto, freeze the fused streaming path
            # into the plan — the TC inner loop dispatches through the
            # same kernel, and ops without a fused path degrade it to
            # auto, so the frozen choice is safe plan-wide. Quantized
            # winners freeze as plain "fused": the fit has no frozen
            # low-precision buffers (those are a serve-time artifact).
            if impl == "auto" and executor not in SHARDED_EXECUTORS:
                ta = tune.tuned_params("assign", dtype=dt, nq=n0, p=n0,
                                       d=d0, k=max(t - 1, 1))
                if str(ta.get("impl", "")).startswith("fused"):
                    impl = "fused"

    if streaming_input:
        validate_reduction_params(t, m, min_m=1, driver=driver)
        if chunk_n:
            validate_reduction_params(t, m, n=chunk_n, min_m=1, driver=driver)
    else:
        validate_reduction_params(t, m, n=data.shape[0], driver=driver)

    return FitPlan(
        t=int(t), m=int(m), backend=backend, executor=executor, key=key,
        weighted=weighted, use_mass_in_backend=use_mass_in_backend,
        impl=impl, knn_block=knn_block, block_q=block_q, block_k=block_k,
        n_blocks=n_blocks, chunk_n=chunk_n,
        reservoir_n=reservoir_n, prefetch_depth=int(prefetch_depth),
        donate_stream=bool(donate_stream), mesh=mesh, axis_name=axis_name,
        min_points=min_points, weights=weights, valid=valid, driver=driver,
        backend_kwargs=dict(backend_kwargs),
    )


# ---------------------------------------------------------------------------
# the planner epilogue: backend finalize + label back-out (once, here)
# ---------------------------------------------------------------------------


def _finalize_backend(
    plan: FitPlan, red: Reduction
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Label the final prototype buffer: registry resolution, mass
    weighting, and ``-1`` masking of invalid rows — identical for every
    executor. Sharded executors keep ``backend="kmeans"`` on the mesh
    (:func:`repro.core.distributed.kmeans_sharded`); any other backend runs
    single-device on the already-reduced prototype set (O(n/(t*)^m) rows —
    the raw points are still never gathered). Returns the labels and the
    backend's counters: ``lloyd_iters`` where its result has ``.iters``."""
    _, key_backend = plan.split_keys()
    w = red.mass if plan.use_mass_in_backend else None
    kwargs = dict(plan.backend_kwargs)
    counters: Dict[str, jax.Array] = {}
    if plan.executor in SHARDED_EXECUTORS and plan.backend == "kmeans":
        from repro.core.distributed import kmeans_sharded  # lazy: no cycle

        k = kwargs.pop("k", 3)
        iters = kwargs.pop("iters", 100)
        proto_labels = kmeans_sharded(
            red.protos, k, valid=red.valid,
            weights=jnp.ones_like(red.mass) if w is None else w,
            key=key_backend, mesh=plan.mesh, axis_name=plan.axis_name,
            iters=iters, impl=plan.impl, n_blocks=plan.shard_multiple(),
            **kwargs)
    else:
        fn = resolve_backend(plan.backend)
        protos, pvalid, pw = red.protos, red.valid, w
        if plan.executor in SHARDED_EXECUTORS:
            # the host backend cannot consume sharded arrays: gather the
            # (small) prototype set once, after the sharded reduction
            protos = jax.device_get(protos)  # repro: allow[HS201]: sharded epilogue gather
            pvalid = jax.device_get(pvalid)  # repro: allow[HS201]: sharded epilogue gather
            pw = None if pw is None else jax.device_get(pw)  # repro: allow[HS201]: sharded epilogue gather
        out = fn(protos, valid=pvalid, weights=pw, key=key_backend,
                 impl=plan.impl, **kwargs)
        proto_labels = getattr(out, "labels", out)
        iters = getattr(out, "iters", None)
        if iters is not None:
            counters["lloyd_iters"] = iters
    return jnp.where(red.valid, proto_labels, -1).astype(jnp.int32), counters


def _plan_scope(plan: FitPlan):
    """The execution config scope (§14): the plan's resolved tile knobs
    pinned, the tune policy clamped to a non-measuring mode (``onthefly``
    → ``cached``; the planner may measure, execution never does). Opening
    the scope is idempotent — nesting it re-applies the same overrides —
    which is what lets the online lifecycle re-run the epilogue under a
    scope bit-identical to the one the executor originally ran in."""
    exec_tune = "off" if runtime.active().tune == "off" else "cached"
    return runtime.configure(block_q=plan.block_q, block_k=plan.block_k,
                             tune=exec_tune)


def finalize_reduction(plan: FitPlan, red: Reduction) -> FitResult:
    """The planner epilogue on an already-produced :class:`Reduction`:
    backend finalize + label back-out + the canonical result — exactly
    what :func:`execute_plan` runs after its executor returns. Split out
    so the online lifecycle (:class:`repro.serve.lifecycle.OnlineFitter`)
    can re-finalize a live reservoir snapshot into a fresh
    :class:`FitResult` through the identical code path."""
    with _plan_scope(plan):
        proto_labels, backend_counters = _finalize_backend(plan, red)
    counters = {**(red.counters or {}), **backend_counters}
    if red.spill is not None:
        return FitResult(
            executor=plan.executor, protos=red.protos, proto_mass=red.mass,
            proto_valid=red.valid, proto_labels=proto_labels,
            n_prototypes=red.n_prototypes, spill=red.spill, counters=counters)
    if red.assignments:
        labels = compose_assignments(red.assignments, proto_labels)
    else:  # m == 0 or early-stop before level 0: backend ran on x itself
        labels = proto_labels
    labels = labels[: red.n0].astype(jnp.int32)
    return FitResult(
        executor=plan.executor, protos=red.protos, proto_mass=red.mass,
        proto_valid=red.valid, proto_labels=proto_labels,
        n_prototypes=red.n_prototypes, assignments=red.assignments,
        labels=labels, counters=counters)


def execute_plan(plan: FitPlan, data: Any) -> FitResult:
    """Run the plan's executor, then the shared epilogue.

    The executor (and the backend epilogue) run under a config scope
    pinning the plan's resolved ``block_q``/``block_k``, so trace-time
    kernel-tile reads default to what :func:`plan_fit` froze rather than
    whatever the ambient config says by the time data starts moving. The
    tune policy is also clamped to a non-measuring mode (``onthefly`` →
    ``cached``): the planner may measure, execution never does. Note the
    precise contract (§14): the plan's own knobs are frozen, while the
    per-shape ops-level lookups stay live against the cache — epoch-keyed,
    so deeper ITIS levels keep their finer-grained winners and any cache
    mutation retraces correctly. With tuning off both pins are no-ops.
    """
    with _plan_scope(plan):
        red = resolve_executor(plan.executor)(plan, data)
    with jax.profiler.TraceAnnotation("repro.fit.finalize"):
        return finalize_reduction(plan, red)


def fit(
    data: Any,
    t: int,
    m: int,
    backend: Union[str, BackendFn] = "kmeans",
    **kwargs,
) -> FitResult:
    """One ``fit()`` over in-memory, sharded, streaming, and composed
    execution — the public entry point (``repro.fit``).

    ``data`` is either a resident (n, d) array or any iterable of host
    chunks (bare (c, d) arrays or ``(chunk, n_valid)`` pairs). The plan
    resolves every dispatch default from the active runtime config and
    picks the executor from the input type and the mesh; pass
    ``executor="memory" | "sharded" | "streaming" | "streaming_sharded"``
    (or configure ``runtime.configure(executor=...)``) to pin one. All
    :func:`plan_fit` keywords are accepted; unknown keywords flow to the
    backend clusterer.

    Returns the canonical :class:`FitResult`.
    """
    # host span names are a contract with the benchmark (DESIGN.md §20)
    with jax.profiler.TraceAnnotation("repro.fit"):
        with jax.profiler.TraceAnnotation("repro.fit.plan"):
            plan = plan_fit(data, t, m, backend, **kwargs)
        return execute_plan(plan, data)
