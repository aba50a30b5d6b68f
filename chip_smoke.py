#!/usr/bin/env python3
"""Run the IHTC fit -> index -> serve path once on a TPU, and check it.

    python chip_smoke.py              # one chip, SIFT1M shape
    python chip_smoke.py --chips 4    # sharded fit on 4 chips vs memory fit

One chip: n = 1,000,000 points of d = 128 (the SIFT1M base-set shape,
TEXMEX, Jegou et al. 2011), f32 and resident in HBM, drawn on the device
from a seeded 256-component Gaussian mixture. ``repro.fit(x, t=2, m=4,
"kmeans", k=256)`` runs the memory executor: level-0 kNN through the fused
Pallas kernel, prototype reduction through ``segment_sum`` and k-means
through ``pairwise_sq_l2``. ``ClusterIndex.build`` freezes the result and a
warmed ``ClusterService`` labels 10,000 fresh queries sent as requests of
mixed sizes, compared with a plain jnp reference (argmin over
``kernels/ref.pairwise_sq_l2``).

``--chips 4`` runs only the ``sharded`` executor over a 4-chip data mesh
and the ``memory`` fit it is compared with, on one chip, on the same data:
n = 65,536 at d = 128 from a 16-component mixture, k = 16. The sharded
ring kNN writes an (n/4)² distance block per ring step, which bounds n.

Every phase checks its result and raises on failure. The last line of
stdout is ``{"ok": true, "device": {...}}`` and is printed only after every
phase passed on a TPU. Without a TPU the script exits 2 and prints no
result. ``--rehearse`` runs the same phases on the CPU backend with the
Pallas kernels in interpret mode (use a small ``--n``), then exits 3
without a result: it tests the control flow and the checks, not the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

K = 256           # clusters, and components of the generating mixture
# --chips 4: 16 components at n = 65,536 keep ~4,000 points per component,
# as 256 components do at 10^6. With fewer points per component the ITIS
# levels leave about one prototype per component (in d = 128 a centroid is
# the nearest neighbour of most of its cluster) and the last level merges
# components, which tests the data, not the executors.
K_FOUR = 16
T, M = 2, 4       # ITIS threshold and levels: n / 2**4 prototypes at most
MIN_ACCURACY = 0.9
MIN_AGREEMENT = 0.999
REQUEST_SIZES = (1, 17, 250, 1000, 2048, 2500, 4184)  # 10,000 queries


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str, code: int = 1) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def mixture(key, d, comps):
    """Centers and per-feature scales of the generating mixture (the
    ``PointStreamConfig(kind="blobs")`` recipe, drawn on the device)."""
    import jax

    kc, ks = jax.random.split(key)
    centers = 4.0 * jax.random.normal(kc, (comps, d))
    scales = jax.random.uniform(ks, (comps, d), minval=0.5, maxval=1.5)
    return centers, scales


def draw(key, centers, scales, n):
    """n points of the mixture and the component each came from."""
    import jax

    @jax.jit
    def go(key, centers, scales):
        ka, kn = jax.random.split(key)
        comp = jax.random.randint(ka, (n,), 0, centers.shape[0])
        noise = jax.random.normal(kn, (n, centers.shape[1]))
        return centers[comp] + noise * scales[comp], comp

    return go(key, centers, scales)


def kernels_in(compiled) -> list:
    """Names of the Pallas TPU kernels a compiled program calls."""
    names = re.findall(r'%([\w.-]+) = .*custom_call_target="tpu_custom_call"',
                       compiled.as_text())
    return sorted({re.sub(r"\.\d+$", "", n) for n in names})


def require_kernels(what: str, compiled, rehearse: bool) -> None:
    names = kernels_in(compiled)
    log(f"kernels[{what}]: {names or 'none (interpret mode)'}")
    if not rehearse:
        check(bool(names), f"the compiled {what} program calls no "
                           f"tpu_custom_call: the kernels did not run")


def fit_checks(res, comp, n, k, what: str):
    """Every point labelled, the prototype bound, mass conserved, each
    cluster at least t**m units; returns accuracy against the mixture."""
    import jax.numpy as jnp

    from repro.cluster.metrics import clustering_accuracy

    labels = np.asarray(res.labels)
    n_protos = int(res.n_prototypes)
    check(labels.shape == (n,), f"{what}: labels shape {labels.shape}")
    check(labels.min() >= 0, f"{what}: {int((labels < 0).sum())} unlabelled")
    check(n_protos <= n // T**M, f"{what}: {n_protos} prototypes > n/{T**M}")
    mass = float(jnp.sum(jnp.where(res.proto_valid, res.proto_mass, 0.0)))
    check(abs(mass - n) < 1e-3 * n, f"{what}: prototype mass {mass} != {n}")
    sizes = np.bincount(labels)
    check(sizes[sizes > 0].min() >= T**M,
          f"{what}: a cluster smaller than t**m = {T**M}")
    check(bool(jnp.all(jnp.isfinite(res.protos))), f"{what}: non-finite")
    acc = clustering_accuracy(np.asarray(comp), labels, k)
    log(f"{what}: n_prototypes={n_protos} clusters={int((sizes > 0).sum())} "
        f"accuracy={acc}")
    check(acc > MIN_ACCURACY, f"{what}: accuracy {acc} <= {MIN_ACCURACY}")
    return labels


def timed_fit(x, key, k, **kw):
    import jax

    import repro

    t0 = time.perf_counter()
    res = repro.fit(x, T, M, "kmeans", k=k, key=key, **kw)
    jax.block_until_ready((res.labels, res.protos))
    return res, time.perf_counter() - t0


def one_chip(args, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.cluster.kmeans import kmeans
    from repro.core.index import ClusterIndex
    from repro.core.itis import itis_step
    from repro.kernels import ref
    from repro.serve import ClusterService

    n, d = args.n, args.d
    key = jax.random.PRNGKey(args.seed)
    kmix, kx, kq, kfit = jax.random.split(key, 4)

    t0 = time.perf_counter()
    centers, scales = mixture(kmix, d, K)
    x, comp = jax.block_until_ready(draw(kx, centers, scales, n))
    log(f"data: n={n} d={d} dtype={x.dtype} bytes={x.nbytes} "
        f"components={K} seconds={time.perf_counter() - t0}")

    # fit: the first call compiles, the second is warm
    res, cold = timed_fit(x, kfit, K)
    res, warm = timed_fit(x, kfit, K)
    log(f"fit: executor={res.executor} t={T} m={M} k={K} "
        f"setup_seconds={cold - warm} (cold {cold} - warm {warm})")
    log(f"fit: warm_seconds={warm}")
    fit_checks(res, comp, n, K, "fit")

    # the compiled fit programs: level-0 ITIS step and the k-means backend
    step = jax.jit(lambda x, mass, valid, key: itis_step(
        x, mass, valid, T, key=key, n_out=n // T))
    ones = jnp.ones((n,), jnp.float32)
    require_kernels("fit level-0 step", step.lower(
        x, ones, ones > 0, kfit).compile(), rehearse)
    km = jax.jit(lambda p, v, w, key: kmeans(p, K, valid=v, weights=w,
                                             key=key).labels)
    require_kernels("fit k-means", km.lower(
        res.protos, res.proto_valid, res.proto_mass, kfit).compile(),
        rehearse)

    # serve: build the index, warm the bucket ladder, send mixed requests
    index = ClusterIndex.build(res)
    svc = ClusterService(index)
    t0 = time.perf_counter()
    svc.warmup()
    log(f"serve: buckets={svc.buckets} warmup_seconds="
        f"{time.perf_counter() - t0}")
    q, _ = jax.block_until_ready(draw(kq, centers, scales,
                                      sum(REQUEST_SIZES)))
    t0 = time.perf_counter()
    parts, lo = [], 0
    for size in REQUEST_SIZES:
        parts.append(svc.assign(q[lo:lo + size]))
        lo += size
    got = jax.block_until_ready(jnp.concatenate(parts))
    log(f"serve: requests={len(REQUEST_SIZES)} queries={lo} "
        f"seconds={time.perf_counter() - t0} stats={svc.stats}")
    require_kernels("assign", jax.jit(lambda idx, q: idx.assign(q)).lower(
        index, q[:svc.buckets[-1]]).compile(), rehearse)

    # plain reference: argmin over the jnp distance matrix, in chunks; a
    # label agrees if it belongs to a prototype tied for the minimum
    @jax.jit
    def agree(qc, lab):
        dist = ref.pairwise_sq_l2(qc, index.protos, y_valid=index.proto_valid)
        dmin = jnp.min(dist, axis=1, keepdims=True)
        exact = index.proto_labels[jnp.argmin(dist, axis=1)] == lab
        tied = dist <= dmin * (1.0 + 1e-5)
        same = index.proto_labels[None, :] == lab[:, None]
        return jnp.sum(exact), jnp.sum(jnp.any(tied & same, axis=1))

    exact = ties_ok = 0
    for s in range(0, lo, 2000):
        e, o = agree(q[s:s + 2000], got[s:s + 2000])
        exact, ties_ok = exact + int(e), ties_ok + int(o)
    log(f"serve: agreement={ties_ok / lo} exact_argmin_agreement="
        f"{exact / lo} (jnp reference, ties allowed)")
    check(ties_ok / lo >= MIN_AGREEMENT,
          f"assign agreement {ties_ok / lo} < {MIN_AGREEMENT}")


def four_chips(args, rehearse: bool) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.cluster.metrics import clustering_accuracy
    from repro.core import make_data_mesh

    devices = jax.devices()
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} devices")
    mesh = make_data_mesh(args.chips)
    n, d = args.n, args.d
    key = jax.random.PRNGKey(args.seed)
    kmix, kx, kfit = jax.random.split(key, 3)
    centers, scales = mixture(kmix, d, K_FOUR)
    x, comp = jax.block_until_ready(draw(kx, centers, scales, n))
    log(f"data: n={n} d={d} components={K_FOUR} k={K_FOUR}")

    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    shards = {s.device.id: s.data.shape for s in xs.addressable_shards}
    log(f"shards: sharding={xs.sharding} per_device_rows={shards}")
    check(len(shards) == args.chips
          and all(r == (n // args.chips, d) for r in shards.values()),
          f"points are not split over {args.chips} devices: {shards}")
    for dev in mesh.devices.flat:
        stats = dev.memory_stats() or {}
        log(f"memory: device={dev.id} bytes_in_use="
            f"{stats.get('bytes_in_use', 'not reported')}")

    r_sh, t_sh = timed_fit(xs, kfit, K_FOUR, executor="sharded", mesh=mesh)
    r_sh, w_sh = timed_fit(xs, kfit, K_FOUR, executor="sharded", mesh=mesh)
    log(f"sharded: chips={args.chips} cold_seconds={t_sh} "
        f"warm_seconds={w_sh}")
    lab_sh = fit_checks(r_sh, comp, n, K_FOUR, "sharded")

    x1 = jax.device_put(x, devices[0])
    r_mem, t_mem = timed_fit(x1, kfit, K_FOUR, executor="memory")
    r_mem, w_mem = timed_fit(x1, kfit, K_FOUR, executor="memory")
    log(f"memory: chips=1 cold_seconds={t_mem} warm_seconds={w_mem}")
    lab_mem = fit_checks(r_mem, comp, n, K_FOUR, "memory")

    agreement = clustering_accuracy(lab_mem, lab_sh, K_FOUR)
    log(f"sharded_vs_memory: label agreement after matching={agreement}")
    check(agreement >= 0.99, f"sharded and memory fits agree on only "
                             f"{agreement} of points")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--n", type=int, default=None,
                    help="points (default 1,000,000; 65,536 with --chips 4)")
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU backend in interpret mode; never "
                         "prints a result and exits 3")
    args = ap.parse_args()
    if args.n is None:
        args.n = 1_000_000 if args.chips == 1 else 65_536

    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        fail(f"JAX backend is {backend!r}, not a TPU", code=2)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import runtime
    from repro.runtime.compile_cache import cache_stats, enable_compile_cache

    cfg = runtime.active()
    if cfg.impl == "ref" or cfg.interpret:
        fail(f"runtime config pins impl={cfg.impl!r} interpret="
             f"{cfg.interpret!r} (REPRO_IMPL / REPRO_INTERPRET): the Pallas "
             f"kernels would not run")
    log(f"compile_cache: dir={enable_compile_cache()}")
    dev = jax.devices()[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")

    # the tuning cache lives outside the checkout: dispatch runs on the
    # hand-picked constants; a rehearsal forces the kernels in interpret mode
    scope = dict(tune="off")
    if args.rehearse:
        scope.update(impl="pallas", interpret=True)
    t0 = time.perf_counter()
    with runtime.configure(**scope):
        (one_chip if args.chips == 1 else four_chips)(args, args.rehearse)
    log(f"total_seconds={time.perf_counter() - t0} "
        f"compile_cache={cache_stats()}")

    if args.rehearse:
        log("rehearsal passed; not a chip run, so no result line")
        sys.exit(3)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
