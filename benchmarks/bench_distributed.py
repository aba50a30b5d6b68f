"""Weak-scaling harness for the sharded IHTC pipeline (DESIGN.md §4).

Sweeps the device count on a forced-multi-device CPU host (the same
``--xla_force_host_platform_device_count`` trick the distribution tests
use): for each device count P a fresh subprocess streams a GMM point cloud
onto a 1-D ``data`` mesh and runs the end-to-end sharded IHTC
(ring-kNN TC → distributed prototype reduce → mesh-aware k-means).

Weak scaling holds n/P fixed (default 8192 points per device, so perfect
scaling is a flat wall-time line); ``--strong`` holds n fixed instead.

Output: one ``distributed_ihtc`` CSV block on stdout (the format every
``bench_table*.py`` uses, consumed by ``benchmarks/run.py``) plus a
``benchmarks/results/BENCH_distributed.json`` trajectory artifact — see
docs/BENCHMARKS.md for the schema and how run.py summarizes these files.

    python benchmarks/run.py --distributed      # via the driver
    python -m benchmarks.bench_distributed      # standalone sweep
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# benchmark-registry entry (benchmarks/run.py --bench distributed)
BENCH = {
    "name": "distributed",
    "artifact": "BENCH_distributed.json",
    "summary": ("devices", "seconds"),
    "quick": dict(n_per_device=4096),
    "full": lambda mx: dict(n_per_device=min(mx, 65_536)),
}


def _child(devices: int, n: int, t: int, m: int, k: int) -> None:
    """Runs in a subprocess with ``devices`` forced CPU devices; prints one
    JSON result line prefixed with ``RESULT:``."""
    import jax
    import numpy as np

    from benchmarks.common import require_forced_cpu_devices, timed
    from repro.core.distributed import ihtc_sharded, make_data_mesh
    from repro.data import PointStreamConfig, point_chunks, stream_to_mesh

    require_forced_cpu_devices(devices)
    mesh = make_data_mesh()
    cfg = PointStreamConfig(n=n, d=2, chunk=min(n, 65_536), seed=0,
                            kind="gmm")
    t0 = time.perf_counter()
    x, valid = stream_to_mesh(point_chunks(cfg), mesh, cfg.n, cfg.d)
    ingest_s = time.perf_counter() - t0

    def work():
        return ihtc_sharded(x, t, m, "kmeans", k=k, valid=valid, mesh=mesh,
                            key=jax.random.PRNGKey(0))

    res, sec = timed(work, warmup=1, iters=1)
    lab = np.asarray(res.labels)[np.asarray(valid)]
    out = {
        "devices": devices,
        "n": n,
        "n_per_device": n // devices,
        "seconds": round(sec, 4),
        "ingest_seconds": round(ingest_s, 4),
        "n_prototypes": int(res.n_prototypes),
        "clusters": int(len(np.unique(lab[lab >= 0]))),
        "all_assigned": bool((lab >= 0).all()),
    }
    print("RESULT:" + json.dumps(out))


def run(device_counts=(1, 2, 4, 8), n_per_device: int = 8192, *,
        strong_n: int = 0, t: int = 2, m: int = 2, k: int = 3,
        out_path: str = "") -> list:
    """Sweep device counts in subprocesses; returns the per-count rows."""
    from benchmarks.common import print_csv, run_child

    rows = []
    for p in device_counts:
        n = strong_n if strong_n else n_per_device * p
        env = dict(
            os.environ,
            XLA_FLAGS=f"--xla_force_host_platform_device_count={p}",
            PYTHONPATH=os.pathsep.join(
                [os.path.join(_REPO, "src"), _REPO,
                 os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep),
        )
        out = run_child(
            [sys.executable, "-m", "benchmarks.bench_distributed", "--_child",
             str(p), "--n", str(n), "--t", str(t), "--m", str(m),
             "--k", str(k)],
            env, _REPO, timeout=1800)
        line = next(l for l in out.splitlines()
                    if l.startswith("RESULT:"))
        rows.append(json.loads(line[len("RESULT:"):]))

    print_csv(
        "distributed_ihtc",
        [(r["devices"], r["n"], r["seconds"], r["ingest_seconds"],
          r["n_prototypes"], r["clusters"]) for r in rows],
        "devices,n,seconds,ingest_seconds,n_prototypes,clusters",
    )

    mode = "strong" if strong_n else "weak"
    artifact = {
        "name": "distributed_ihtc",
        "mode": mode,
        "t": t, "m": m, "k": k,
        "recorded_unix": round(time.time(), 1),
        "rows": rows,
    }
    path = out_path or os.path.join(RESULTS_DIR, "BENCH_distributed.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"# wrote {os.path.relpath(path, _REPO)}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--_child", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--n", type=int, default=0)
    ap.add_argument("--t", type=int, default=2)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--devices", type=str, default="1,2,4,8")
    ap.add_argument("--n-per-device", type=int, default=8192)
    ap.add_argument("--strong", action="store_true",
                    help="fix total n (=--n) instead of n per device")
    args = ap.parse_args()

    if args._child:
        _child(args._child, args.n, args.t, args.m, args.k)
        return
    counts = tuple(int(c) for c in args.devices.split(","))
    run(counts, args.n_per_device,
        strong_n=(args.n or args.n_per_device * max(counts)) if args.strong
        else 0,
        t=args.t, m=args.m, k=args.k)


if __name__ == "__main__":
    main()
