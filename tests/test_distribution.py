"""Distribution correctness on a real multi-device (8× CPU) mesh.

These tests run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_
device_count=8 (conftest keeps the main test process at 1 device), and
assert numerical equality between sharded and single-device execution for:
pjit'd train step, ring-kNN vs exact kNN, compressed psum, sharded TC, the
end-to-end sharded IHTC pipeline (bit-for-bit label parity), and streamed
multi-device ingestion.
"""
import os
import subprocess
import sys

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_SCRIPT_COMMON = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
assert len(jax.devices()) == 8
"""


def _run(body: str):
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT_COMMON + body],
        capture_output=True, text=True, timeout=420,
        env=dict(os.environ, PYTHONPATH=SRC),
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_sharded_train_step_matches_single_device():
    out = _run("""
from repro.configs import ARCHS, SHAPES, smoke_config
from repro.models import build
from repro.models.transformer import ShardingPlan
from repro.data import make_batch
from repro.train import OptConfig, init_opt_state, make_train_step
from repro.launch.mesh import make_debug_mesh

cfg = smoke_config(ARCHS["qwen2.5-32b"])
bundle = build(cfg)
params = bundle.init(jax.random.PRNGKey(0))
opt = init_opt_state(params)
batch = make_batch(cfg, SHAPES["train_4k"], 0, batch_override=4, seq_override=16)
ocfg = OptConfig(peak_lr=1e-2, warmup_steps=2, decay_steps=10)

# single device
step1 = jax.jit(make_train_step(bundle, ocfg))
p1, _, m1 = step1(params, opt, batch)

# 2x4 mesh, fully sharded
mesh = make_debug_mesh(2, 4)
pspecs = bundle.param_specs(tp="model", tp_size=4)
plan = ShardingPlan(resid=P("data", None, None), logits=P("data", None, "model"))
shard = lambda tree, specs: jax.tree_util.tree_map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
    is_leaf=lambda x: isinstance(x, P) or hasattr(x, "shape"))
with mesh:
    ps = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs)
    bs = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), batch)
    step2 = jax.jit(make_train_step(bundle, ocfg, plan=plan))
    p2, _, m2 = step2(ps, opt, bs)
assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-2, (m1["loss"], m2["loss"])
for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
    # bf16 matmuls reduce in different orders across shardings: tolerate
    # ~1 bf16 ulp of drift on a handful of elements
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               atol=1.5e-2)
print("TRAIN-STEP-PARITY-OK")
""")
    assert "TRAIN-STEP-PARITY-OK" in out


def test_ring_knn_matches_exact():
    out = _run("""
from functools import partial
from jax import shard_map
from repro.core.knn import ring_knn, knn_graph

mesh = jax.make_mesh((8,), ("data",))
n, d, k = 64, 3, 4
x = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)

fn = shard_map(
    partial(ring_knn, k=k, axis_name="data", impl="ref"),
    mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
)
rd, ri = fn(x)
wd, wi = knn_graph(x, k, impl="ref")
np.testing.assert_allclose(np.asarray(rd), np.asarray(wd), rtol=1e-5, atol=1e-5)
np.testing.assert_array_equal(np.asarray(ri), np.asarray(wi))
print("RING-KNN-OK")
""")
    assert "RING-KNN-OK" in out


def test_compressed_psum_error_feedback():
    out = _run("""
from functools import partial
from jax import shard_map
from repro.train.compression import compressed_psum, psum_with_error_feedback

mesh = jax.make_mesh((8,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 128), jnp.float32)

# one-shot compressed mean close to the true mean
got = shard_map(partial(compressed_psum, axis_name="pod"), mesh=mesh,
                in_specs=P("pod", None), out_specs=P("pod", None))(x)
want = jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)
rel = float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-9))
assert rel < 0.02, rel

# error feedback: accumulated mean over steps converges (bias ~ O(q^2))
def step(x, err):
    return shard_map(partial(psum_with_error_feedback, axis_name="pod"),
                     mesh=mesh, in_specs=(P("pod", None), P("pod", None)),
                     out_specs=(P("pod", None), P("pod", None)))(x, err)
err = jnp.zeros_like(x)
tot = jnp.zeros_like(x)
for _ in range(16):
    o, err = step(x, err)
    tot = tot + o
avg_err = float(jnp.max(jnp.abs(tot / 16 - want)))
one_err = float(jnp.max(jnp.abs(got - want)))
assert avg_err < one_err * 0.6, (avg_err, one_err)
print("COMPRESSED-PSUM-OK")
""")
    assert "COMPRESSED-PSUM-OK" in out


def test_sharded_itis_pipeline():
    """Per-shard TC → prototype all-gather (hierarchical ITIS) preserves the
    size guarantee and the reduction factor on an 8-way mesh."""
    out = _run("""
from functools import partial
from jax import shard_map
from repro.core import threshold_clustering, itis

mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(256, 2)), jnp.float32)

def shard_tc(x_local):
    r = threshold_clustering(x_local, 2, key=jax.random.PRNGKey(0))
    return r.labels, r.n_clusters.reshape(1)

# check_vma=False: the MIS while-loop carries mix replicated and per-shard values
labels, ncs = shard_map(shard_tc, mesh=mesh, in_specs=P("data", None),
                        out_specs=(P("data"), P("data")), check_vma=False)(x)
labels = np.asarray(labels).reshape(8, 32)
for s in range(8):
    lab = labels[s]
    sizes = np.bincount(lab[lab >= 0])
    assert sizes[sizes > 0].min() >= 2, s
assert int(np.asarray(ncs).sum()) <= 128
print("SHARDED-TC-OK")
""")
    assert "SHARDED-TC-OK" in out


def test_sharded_ihtc_matches_single_device():
    """The tentpole parity contract (DESIGN.md §4.3): the end-to-end sharded
    IHTC — ring-kNN TC, distributed Luby MIS, folded prototype reduce,
    mesh-aware k-means — produces labels *bit-for-bit identical* to the
    single-device ihtc() at t=3, m=2 on an 8-device mesh. n=576 divides
    evenly through both levels (576 → 192 → 64), so both paths compute in
    identical buffers."""
    out = _run("""
from repro.core import ihtc
from repro.core.distributed import ihtc_sharded, make_data_mesh

rng = np.random.default_rng(0)
mus = np.array([[1, 2], [7, 8], [3, 5]], float)
sds = np.array([[1, 0.5], [2, 1], [3, 4]], float) ** 0.5
comp = rng.choice(3, size=576, p=[0.5, 0.3, 0.2])
x = jnp.asarray(mus[comp] + rng.normal(size=(576, 2)) * sds[comp], jnp.float32)

res1 = ihtc(x, 3, 2, "kmeans", k=3, key=jax.random.PRNGKey(7))
res2 = ihtc_sharded(x, 3, 2, "kmeans", k=3, key=jax.random.PRNGKey(7),
                    mesh=make_data_mesh())
l1, l2 = np.asarray(res1.labels), np.asarray(res2.labels)
assert l1.min() >= 0
assert np.array_equal(l1, l2), (l1 != l2).sum()
p1, p2 = np.asarray(res1.protos), np.asarray(res2.protos)
assert np.array_equal(p1.view(np.uint32), p2.view(np.uint32))
assert int(res1.n_prototypes) == int(res2.n_prototypes)
# the mesh= kwarg on the public API dispatches to the same path
res3 = ihtc(x, 3, 2, "kmeans", k=3, key=jax.random.PRNGKey(7),
            mesh=make_data_mesh())
assert np.array_equal(l1, np.asarray(res3.labels))
# dispatch resolved via RuntimeConfig (no kwargs): same bits again, and a
# configured mesh shards the plain ihtc() call
from repro import runtime
with runtime.configure(mesh=make_data_mesh()):
    res4 = ihtc(x, 3, 2, "kmeans", k=3, key=jax.random.PRNGKey(7))
assert np.array_equal(l1, np.asarray(res4.labels))
assert np.array_equal(p1.view(np.uint32),
                      np.asarray(res4.protos).view(np.uint32))
# the fitted index serves the mesh-fitted result identically; batch 100
# is not divisible by the 8 devices (exercises the shard-pad path), and
# assign under a configured mesh matches the single-device assign
from repro.core import ClusterIndex
idx1 = ClusterIndex.build(res1)
idx2 = ClusterIndex.build(res2)
q = x[:100]
want = np.asarray(idx1.assign(q))
assert np.array_equal(want, np.asarray(idx2.assign(q)))
with runtime.configure(mesh=make_data_mesh()):
    got = np.asarray(idx2.replicate(make_data_mesh()).assign(q))
assert np.array_equal(want, got)
print("SHARDED-IHTC-PARITY-OK")
""")
    assert "SHARDED-IHTC-PARITY-OK" in out


def test_sharded_ihtc_padded_sizes_and_guarantee():
    """Non-divisible n exercises the validity-masked level padding: the
    (t*)^m size guarantee and mass conservation must still hold."""
    out = _run("""
from repro.core.distributed import ihtc_sharded, itis_sharded, make_data_mesh

rng = np.random.default_rng(1)
x = jnp.asarray(rng.normal(size=(500, 3)), jnp.float32)
mesh = make_data_mesh()
r = itis_sharded(x, 2, 3, mesh=mesh)
assert abs(float(jnp.sum(jnp.where(r.valid, r.mass, 0.0))) - 500) < 1e-3
res = ihtc_sharded(x, 2, 3, "kmeans", k=3, mesh=mesh)
lab = np.asarray(res.labels)
assert lab.shape == (500,) and lab.min() >= 0
sizes = np.bincount(lab)
assert sizes[sizes > 0].min() >= 2 ** 3
print("SHARDED-IHTC-PADDED-OK")
""")
    assert "SHARDED-IHTC-PADDED-OK" in out


def test_streamed_ingestion_feeds_sharded_pipeline():
    """data.stream_to_mesh places host-sized chunks shard-by-shard; the
    assembled array equals the direct concatenation and drives IHTC."""
    out = _run("""
from repro.data import PointStreamConfig, point_chunks, stream_to_mesh
from repro.core.distributed import ihtc_sharded, make_data_mesh

mesh = make_data_mesh()
cfg = PointStreamConfig(n=5000, d=2, chunk=700, seed=3, kind="gmm")
x, valid = stream_to_mesh(point_chunks(cfg), mesh, cfg.n, cfg.d)
assert x.shape[0] % 8 == 0 and x.shape[1] == 2
full = np.concatenate([c for c in point_chunks(cfg)])
assert np.array_equal(np.asarray(x)[np.asarray(valid)], full)
res = ihtc_sharded(x, 2, 2, "kmeans", k=3, valid=valid, mesh=mesh)
lab = np.asarray(res.labels)
v = np.asarray(valid)
assert lab[v].min() >= 0 and (lab[~v] == -1).all()
print("STREAM-INGEST-OK")
""")
    assert "STREAM-INGEST-OK" in out


def test_fit_executor_matrix_bit_identical():
    """The planner's equivalence contract (DESIGN.md §13): on an aligned
    config — one chunk-aligned level-0 buffer, a non-overflowing reservoir,
    every level size dividing the 8-way shard multiple — all four executors
    (memory / sharded / streaming / streaming_sharded) produce bit-identical
    labels, prototypes and masses through one repro.fit() entry point."""
    out = _run("""
import repro
from repro.core import make_data_mesh

rng = np.random.default_rng(0)
mus = np.array([[1, 2], [7, 8], [3, 5]], float)
sds = np.array([[1, 0.5], [2, 1], [3, 4]], float) ** 0.5
comp = rng.choice(3, size=512, p=[0.5, 0.3, 0.2])
x_np = (mus[comp] + rng.normal(size=(512, 2)) * sds[comp]).astype(np.float32)
x = jnp.asarray(x_np)
mesh = make_data_mesh()
key = jax.random.PRNGKey(7)

r_mem = repro.fit(x, 2, 2, "kmeans", k=3, key=key, executor="memory")
r_sh = repro.fit(x, 2, 2, "kmeans", k=3, key=key, executor="sharded",
                 mesh=mesh)
r_st = repro.fit(iter([x_np]), 2, 2, "kmeans", k=3, key=key,
                 executor="streaming", chunk_n=512, reservoir_n=1024)
r_co = repro.fit(iter([x_np]), 2, 2, "kmeans", k=3, key=key,
                 executor="streaming_sharded", chunk_n=512,
                 reservoir_n=1024, mesh=mesh)
assert [r.executor for r in (r_mem, r_sh, r_st, r_co)] == [
    "memory", "sharded", "streaming", "streaming_sharded"]

want = np.asarray(r_mem.labels)
assert want.min() >= 0
assert np.array_equal(want, np.asarray(r_sh.labels))
assert np.array_equal(want, r_st.labels_for(0))
assert np.array_equal(want, r_co.labels_for(0))
pm = np.asarray(r_mem.protos).view(np.uint32)
mm = np.asarray(r_mem.proto_mass).view(np.uint32)
for r in (r_sh, r_st, r_co):
    assert np.array_equal(pm, np.asarray(r.protos).view(np.uint32))
    assert np.array_equal(mm, np.asarray(r.proto_mass).view(np.uint32))
    assert int(r.n_prototypes) == int(r_mem.n_prototypes)

# the frozen artifact serves identically from every executor's result
q = x[:100]
want_q = np.asarray(r_mem.to_index().assign(q))
for r in (r_sh, r_st, r_co):
    assert np.array_equal(want_q, np.asarray(r.to_index().assign(q)))
print("FIT-MATRIX-OK")
""")
    assert "FIT-MATRIX-OK" in out


def test_pipelined_ingest_matrix_bit_identical():
    """The §18 extension of the executor matrix: the streaming executors
    stay bit-identical to the in-memory reference on the aligned config —
    and to their own serial loop on a cascading multi-chunk stream — for
    every prefetch_depth in {0, 1, 3} x donation on/off, on a real 8-way
    mesh."""
    out = _run("""
import repro
from repro.core import make_data_mesh

rng = np.random.default_rng(0)
mus = np.array([[1, 2], [7, 8], [3, 5]], float)
sds = np.array([[1, 0.5], [2, 1], [3, 4]], float) ** 0.5
comp = rng.choice(3, size=512, p=[0.5, 0.3, 0.2])
x_np = (mus[comp] + rng.normal(size=(512, 2)) * sds[comp]).astype(np.float32)
mesh = make_data_mesh()
key = jax.random.PRNGKey(7)
GRID = [(dep, don) for dep in (0, 1, 3) for don in (False, True)]

# aligned single-buffer stream: every cell == the in-memory bits
want = repro.fit(jnp.asarray(x_np), 2, 2, "kmeans", k=3, key=key,
                 executor="memory")
wl = np.asarray(want.labels)
wp = np.asarray(want.protos).view(np.uint32)
wm = np.asarray(want.proto_mass).view(np.uint32)
for ex, kw in (("streaming", {}), ("streaming_sharded", {"mesh": mesh})):
    for dep, don in GRID:
        r = repro.fit(iter([x_np]), 2, 2, "kmeans", k=3, key=key,
                      executor=ex, chunk_n=512, reservoir_n=1024,
                      prefetch_depth=dep, donate_stream=don, **kw)
        assert np.array_equal(wl, r.labels_for(0)), (ex, dep, don)
        assert np.array_equal(wp, np.asarray(r.protos).view(np.uint32)), (ex, dep, don)
        assert np.array_equal(wm, np.asarray(r.proto_mass).view(np.uint32)), (ex, dep, don)

# cascading multi-chunk stream: every cell == that executor's serial loop
n, chunk = 4096, 512
comp2 = rng.choice(3, size=n, p=[0.5, 0.3, 0.2])
y = (mus[comp2] + rng.normal(size=(n, 2)) * sds[comp2]).astype(np.float32)
mk = lambda: iter([y[lo:lo + chunk] for lo in range(0, n, chunk)])
for ex, kw in (("streaming", {}), ("streaming_sharded", {"mesh": mesh})):
    ref = repro.fit(mk(), 2, 2, "kmeans", k=3, key=key, executor=ex,
                    chunk_n=chunk, reservoir_n=640, prefetch_depth=0, **kw)
    assert ref.n_cascades >= 1
    rl = ref.labels()
    rp = np.asarray(ref.protos).view(np.uint32)
    for dep, don in GRID[2:]:
        r = repro.fit(mk(), 2, 2, "kmeans", k=3, key=key, executor=ex,
                      chunk_n=chunk, reservoir_n=640, prefetch_depth=dep,
                      donate_stream=don, **kw)
        assert np.array_equal(rl, r.labels()), (ex, dep, don)
        assert np.array_equal(rp, np.asarray(r.protos).view(np.uint32)), (ex, dep, don)
print("PIPELINED-MATRIX-OK")
""")
    assert "PIPELINED-MATRIX-OK" in out


def test_mesh_place_slab_reshards_device_resident():
    """Satellite regression: _MeshPlacement.place_slab must reshard
    device-resident slabs directly (device_put on a jax array) instead of
    round-tripping through jnp.asarray — an already-replicated slab passes
    through untouched (the device_put no-op fast path), a row-sharded slab
    reshards to the replicated layout bit-for-bit, and host numpy slabs
    still place."""
    out = _run("""
from repro.core.plan import plan_fit
from repro.core.streaming import _MeshPlacement
from repro.core import make_data_mesh

mesh = make_data_mesh()
plan = plan_fit(None, 2, 2, "kmeans", k=3, executor="streaming_sharded",
                chunk_n=64, reservoir_n=128, mesh=mesh)
pl = _MeshPlacement(plan, d=2)
rng = np.random.default_rng(0)
px = rng.normal(size=(64, 2)).astype(np.float32)
pm = np.ones((64,), np.float32)
pv = np.ones((64,), bool)

# host slabs place and replicate
hx, hm, hv = pl.place_slab(px, pm, pv)
assert hx.sharding == pl._rep and hm.sharding == pl._rep
assert np.array_equal(np.asarray(hx), px)

# an already-replicated device slab passes through as the same object
gx, gm, gv = pl.place_slab(hx, hm, hv)
assert gx is hx and gm is hm and gv is hv

# a row-sharded device slab (a sharded level-step output) reshards
# device-to-device, bit-for-bit
sx = jax.device_put(jnp.asarray(px), pl._row)
rx, rm, rv = pl.place_slab(sx, hm, hv)
assert rx.sharding == pl._rep
assert np.array_equal(np.asarray(rx).view(np.uint32), px.view(np.uint32))
print("PLACE-SLAB-OK")
""")
    assert "PLACE-SLAB-OK" in out


def test_composed_executor_multichunk_invariants():
    """The composed streaming+sharded path under real cascade pressure:
    host chunks reduced by sharded level steps into a bounded mesh-sharded
    reservoir must hold coverage, mass conservation, the (t*)^m size
    guarantee and GMM accuracy — and a configured mesh must select it
    automatically for chunk-stream inputs."""
    out = _run("""
import repro
from repro import runtime
from repro.core import make_data_mesh
from repro.cluster.metrics import clustering_accuracy

rng = np.random.default_rng(0)
mus = np.array([[1, 2], [7, 8], [3, 5]], float)
sds = np.array([[1, 0.5], [2, 1], [3, 4]], float) ** 0.5
n, chunk, t, m = 4096, 512, 2, 2
comp = rng.choice(3, size=n, p=[0.5, 0.3, 0.2])
x = (mus[comp] + rng.normal(size=(n, 2)) * sds[comp]).astype(np.float32)
chunks = [x[lo:lo + chunk] for lo in range(0, n, chunk)]

with runtime.configure(mesh=make_data_mesh()):
    res = repro.fit(iter(chunks), t, m, "kmeans", k=3, chunk_n=chunk,
                    reservoir_n=640, key=jax.random.PRNGKey(0))
assert res.executor == "streaming_sharded"
assert res.n_chunks == n // chunk
assert res.n_cascades >= 1  # the bounded reservoir actually cascaded
lab = res.labels()
assert lab.shape == (n,)
assert lab.min() >= 0
mass = np.asarray(res.proto_mass)[np.asarray(res.proto_valid)]
assert abs(mass.sum() - n) < 1e-2
sizes = np.bincount(lab)
assert sizes[sizes > 0].min() >= t ** m
assert clustering_accuracy(comp, lab, 3) > 0.85
# ragged tail + (chunk, n_valid) pair + empty chunk through the same path
pairs = [(x[:256], 256), x[256:512], np.zeros((0, 2), np.float32),
         x[512:700]]
res2 = repro.fit(iter(pairs), 2, 2, "kmeans", k=3, chunk_n=256,
                 mesh=make_data_mesh(), key=jax.random.PRNGKey(2))
assert [len(l) for l in res2.iter_labels()] == [256, 256, 0, 188]
assert res2.labels().min() >= 0
print("COMPOSED-INVARIANTS-OK")
""")
    assert "COMPOSED-INVARIANTS-OK" in out
