"""Distributed IHTC: one ``repro.fit()`` over the data mesh, two ways.

Demonstrates the pod pattern at laptop scale (DESIGN.md §4, §13):

  1. **sharded** — a point stream is fed onto the mesh chunk-by-chunk (no
     full-size host buffer) and the resident sharded array is fit: every
     ITIS level runs under shard_map — ring-kNN TC, distributed Luby-MIS
     seeding, cross-shard prototype reduction, rebalance — and the final
     prototypes are clustered by mesh-aware weighted k-means without ever
     gathering points to one device. Bit-identical to the single-device
     fit when the level sizes divide the device count evenly.
  2. **streaming_sharded** — the composed executor: the same chunks are
     reduced *as they stream* by sharded level steps into a bounded
     mesh-sharded reservoir, so peak device memory stays
     O(chunk + reservoir) while every device still works on every chunk —
     out-of-core and multi-device at once.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/massive_clustering.py --n 65536
"""
import argparse
import os
import sys

if os.environ.get("XLA_FLAGS") is None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, "src")

import time

import jax
import numpy as np


def main():
    import repro
    from repro.cluster.metrics import clustering_accuracy
    from repro.core.distributed import make_data_mesh
    from repro.data import PointStreamConfig, point_chunks, stream_to_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=65_536)
    ap.add_argument("--t", type=int, default=2)
    ap.add_argument("--m", type=int, default=4)
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    n_dev = len(jax.devices())
    mesh = make_data_mesh()
    print(f"devices: {n_dev}; n = {args.n}; t* = {args.t}; m = {args.m}")

    # --- the generative component labels (the stream is a pure function of
    # (seed, chunk), so truth is regenerable, not stored) ---
    cfg = PointStreamConfig(n=args.n, d=2, chunk=16_384, seed=0, kind="gmm")
    rng_truth = []
    for i in range(-(-cfg.n // cfg.chunk)):
        rng = np.random.default_rng(np.random.SeedSequence([cfg.seed, i]))
        c = min(cfg.chunk, cfg.n - i * cfg.chunk)
        rng_truth.append(rng.choice(3, size=c, p=[0.5, 0.3, 0.2]))
    comp = np.concatenate(rng_truth)

    # --- way 1: streamed ingestion to a resident sharded array, then the
    # "sharded" executor (repro.fit picks it from the mesh) ---
    t0 = time.perf_counter()
    x, valid = stream_to_mesh(point_chunks(cfg), mesh, cfg.n, cfg.d)
    print(f"ingest: {time.perf_counter() - t0:.2f}s "
          f"({-(-cfg.n // cfg.chunk)} chunks → {x.sharding.spec})")
    t0 = time.perf_counter()
    res = repro.fit(x, args.t, args.m, "kmeans", k=3, valid=valid,
                    mesh=mesh, key=jax.random.PRNGKey(0))
    jax.block_until_ready(res.labels)
    sec = time.perf_counter() - t0
    print(f"{res.executor} fit: {sec:.2f}s, "
          f"{int(res.n_prototypes)} prototypes at level {args.m}")
    lab = np.asarray(res.labels)[np.asarray(valid)]
    acc = clustering_accuracy(comp, lab, 3)
    print(f"accuracy vs generative components: {acc:.4f}")

    # --- way 2: the composed streaming_sharded executor — same chunks,
    # never resident: O(chunk + reservoir) device memory, every device busy
    t0 = time.perf_counter()
    res2 = repro.fit(point_chunks(cfg), args.t, args.m, "kmeans", k=3,
                     mesh=mesh, chunk_n=cfg.chunk,
                     key=jax.random.PRNGKey(0))
    jax.block_until_ready(res2.proto_labels)
    sec = time.perf_counter() - t0
    print(f"{res2.executor} fit: {sec:.2f}s, {res2.n_chunks} chunks, "
          f"{res2.n_cascades} cascades, "
          f"{int(res2.n_prototypes)} prototypes")
    acc2 = clustering_accuracy(comp, res2.labels(), 3)
    print(f"accuracy vs generative components: {acc2:.4f}")

    # both freeze into the same servable artifact
    index = res2.to_index()
    q = jax.numpy.asarray(next(point_chunks(cfg))[:256])
    labels_q = np.asarray(index.assign(q))
    print(f"online assign of {q.shape[0]} fresh rows → "
          f"{len(np.unique(labels_q[labels_q >= 0]))} clusters")


if __name__ == "__main__":
    main()
