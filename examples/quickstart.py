"""Quickstart: the paper's headline experiment in ~30 lines.

One entry point — ``repro.fit(x_or_chunks, t, m, backend)`` — runs IHTC
(ITIS + k-means) on the paper's Gaussian-mixture benchmark and prints the
time / reduction / accuracy trade-off as the ITIS iteration count m grows,
then freezes the last fit into a ClusterIndex and labels a fresh query
batch online. The same call on a chunk iterator runs the out-of-core
streaming executor (bit-identical here, where the stream is one aligned
buffer). All dispatch knobs flow through the runtime config:
`python examples/quickstart.py --n 100000 --impl ref`
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np


def main():
    import repro
    from repro import runtime
    from repro.cluster.metrics import clustering_accuracy

    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--t", type=int, default=2, help="TC size threshold t*")
    ap.add_argument("--impl", default="auto", choices=("auto", "pallas", "ref"),
                    help="kernel dispatch policy (runtime.configure)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    # the paper's §4 mixture: 3 bivariate Gaussians, weights .5/.3/.2
    rng = np.random.default_rng(0)
    mus = np.array([[1, 2], [7, 8], [3, 5]], float)
    sds = np.array([[1, 0.5], [2, 1], [3, 4]], float) ** 0.5
    comp = rng.choice(3, size=args.n, p=[0.5, 0.3, 0.2])
    x_np = (mus[comp] + rng.normal(size=(args.n, 2)) * sds[comp]).astype(
        np.float32)
    x = jnp.asarray(x_np)

    print(f"n={args.n}, t*={args.t}, impl={args.impl}  (m=0 is plain k-means)")
    print(f"{'m':>3} {'seconds':>9} {'prototypes':>11} {'accuracy':>9}")
    with runtime.configure(impl=args.impl):  # one knob, whole pipeline
        for m in range(0, 5):
            t0 = time.perf_counter()
            res = repro.fit(x, args.t, m, "kmeans", k=3,
                            key=jax.random.PRNGKey(0))
            jax.block_until_ready(res.labels)
            sec = time.perf_counter() - t0
            acc = clustering_accuracy(comp, np.asarray(res.labels), 3)
            print(f"{m:>3} {sec:>9.3f} {int(res.n_prototypes):>11} {acc:>9.4f}")

        # the same fit() over a chunk stream plans the out-of-core executor;
        # on this aligned single-buffer stream it is bit-identical
        streamed = repro.fit(iter([x_np]), args.t, 4, "kmeans", k=3,
                             key=jax.random.PRNGKey(0), chunk_n=args.n)
        same = np.array_equal(streamed.labels_for(0), np.asarray(res.labels))
        print(f"streaming executor ({streamed.executor}): "
              f"bit-identical labels = {same}")

        # freeze the last fit into a servable index and label new points
        index = res.to_index()
        comp_q = rng.choice(3, size=1000, p=[0.5, 0.3, 0.2])
        q = jnp.asarray(mus[comp_q] + rng.normal(size=(1000, 2)) * sds[comp_q],
                        jnp.float32)
        t0 = time.perf_counter()
        labels = jax.block_until_ready(index.assign(q))
        sec = time.perf_counter() - t0
        acc = clustering_accuracy(comp_q, np.asarray(labels), 3)
        print(f"online assign of 1000 fresh queries: {sec:.4f}s "
              f"(accuracy {acc:.4f}, {int(index.n_prototypes)} prototypes)")


if __name__ == "__main__":
    main()
