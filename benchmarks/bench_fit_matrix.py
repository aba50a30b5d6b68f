"""Executor matrix: wall time + peak device memory for all four planned
fit executors at fixed (t, m) over growing n.

One subprocess with a forced multi-device CPU host (the same
``--xla_force_host_platform_device_count`` trick as bench_distributed)
sweeps n and runs ``repro.fit`` once per registered executor —

  * ``memory``              — resident array, one device
  * ``sharded``             — resident array, every device
  * ``streaming``           — host chunks, one device
  * ``streaming_sharded``   — host chunks, every device (the composed path)

— recording wall-clock seconds and the peak live device-buffer footprint
(:func:`benchmarks.common.live_mb`, sampled at every chunk boundary for the
streaming family and over the resident fit for the in-memory family). The
claim under test is the planner's memory contract: both streaming columns
stay O(chunk + reservoir) — flat in n — while the in-memory columns grow
linearly with the resident array and its O(n) level maps.

Writes benchmarks/results/BENCH_fit_matrix.json (schema in
docs/BENCHMARKS.md); discovered and summarized by run.py's benchmark
registry (``--bench fit_matrix``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# direct-run support: repo root for the benchmarks package, src/ for repro
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_REPO, os.path.join(_REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")

EXECUTORS = ("memory", "sharded", "streaming", "streaming_sharded")

# benchmark-registry entry (benchmarks/run.py --bench fit_matrix)
BENCH = {
    "name": "fit_matrix",
    "artifact": "BENCH_fit_matrix.json",
    "summary": ("n", "peak_mb"),
    "quick": dict(ns=(4_096, 8_192, 16_384), chunk=1_024, mode="quick"),
    "full": lambda mx: dict(
        ns=tuple(n for n in (16_384, 65_536, 262_144) if n <= mx) or (mx,),
        chunk=4_096, mode="full"),
}


def _child(devices: int, ns, chunk: int, t: int, m: int, d: int,
           k: int, seed: int) -> None:
    """Runs in a subprocess with ``devices`` forced CPU devices; prints one
    ``RESULT:`` JSON line per (n, executor) cell."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from benchmarks.common import live_mb, require_forced_cpu_devices
    from repro.core import make_data_mesh
    from repro.data import PointStreamConfig, point_chunks

    require_forced_cpu_devices(devices)
    mesh = make_data_mesh()

    def watched(chunks, peak):
        for c in chunks:
            peak[0] = max(peak[0], live_mb())
            yield c

    for n in ns:
        cfg = PointStreamConfig(n=n, d=d, chunk=chunk, seed=seed,
                                kind="blobs", k=k)
        for executor in EXECUTORS:
            streaming = executor.startswith("streaming")
            peak = [0.0]
            if streaming:
                data = watched(point_chunks(cfg), peak)
                kw = dict(chunk_n=chunk)
            else:
                data = jnp.asarray(np.concatenate(list(point_chunks(cfg))))
                kw = {}
            t0 = time.perf_counter()
            res = repro.fit(
                data, t, m, "kmeans", k=k, executor=executor,
                mesh=mesh if executor.endswith("sharded") else None,
                key=jax.random.PRNGKey(seed), **kw)
            jax.block_until_ready(res.proto_labels)
            sec = time.perf_counter() - t0
            # for the in-memory family the resident array + its O(n) level
            # maps are all still live right here — that IS its footprint
            peak[0] = max(peak[0], live_mb())
            labs = np.concatenate(list(res.iter_labels()))
            out = {
                "n": n,
                "executor": executor,
                "devices": devices,
                "seconds": round(sec, 4),
                "points_per_sec": round(n / sec),
                "peak_mb": round(peak[0], 3),
                "n_prototypes": int(res.n_prototypes),
                "all_assigned": bool((labs >= 0).all()),
            }
            del res, data, labs
            print("RESULT:" + json.dumps(out), flush=True)


def run(ns=(4_096, 16_384, 65_536), chunk: int = 2_048, *,
        devices: int = 8, t: int = 2, m: int = 2, d: int = 8, k: int = 4,
        seed: int = 0, mode: str = "quick") -> list:
    """Run the executor matrix in one forced-multi-device subprocess."""
    from benchmarks.common import print_csv, run_child

    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.pathsep.join(
            [os.path.join(_REPO, "src"), _REPO,
             os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep),
    )
    out = run_child(
        [sys.executable, "-m", "benchmarks.bench_fit_matrix", "--_child",
         str(devices), "--ns", ",".join(str(n) for n in ns),
         "--chunk", str(chunk), "--t", str(t), "--m", str(m),
         "--d", str(d), "--k", str(k), "--seed", str(seed)],
        env, _REPO, timeout=3600)
    rows = [json.loads(line[len("RESULT:"):])
            for line in out.splitlines()
            if line.startswith("RESULT:")]

    print_csv(
        "fit_matrix",
        [(r["n"], r["executor"], r["devices"], r["seconds"],
          r["points_per_sec"], r["peak_mb"], r["n_prototypes"],
          r["all_assigned"]) for r in rows],
        "n,executor,devices,seconds,points_per_sec,peak_mb,"
        "n_prototypes,all_assigned",
    )

    os.makedirs(RESULTS, exist_ok=True)
    artifact = {
        "name": "fit_matrix",
        "mode": mode,
        "t": t, "m": m, "d": d, "k": k,
        "chunk_n": chunk,
        "devices": devices,
        "executors": list(EXECUTORS),
        "recorded_unix": round(time.time(), 1),
        "rows": rows,
    }
    path = os.path.join(RESULTS, "BENCH_fit_matrix.json")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"# wrote {os.path.relpath(path, _REPO)}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--_child", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--ns", type=str, default="")
    ap.add_argument("--chunk", type=int, default=2_048)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--t", type=int, default=2)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--d", type=int, default=8)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="tiny sweep for CI smoke")
    args = ap.parse_args()
    ns = (tuple(int(v) for v in args.ns.split(",")) if args.ns
          else (4_096, 16_384, 65_536))
    if args._child:
        _child(args._child, ns, args.chunk, args.t, args.m, args.d,
               args.k, args.seed)
        return
    if args.quick:
        run(**BENCH["quick"], devices=args.devices, t=args.t, m=args.m,
            k=args.k, seed=args.seed)
        return
    run(ns=ns, chunk=args.chunk, devices=args.devices, t=args.t, m=args.m,
        d=args.d, k=args.k, seed=args.seed, mode="cli")


if __name__ == "__main__":
    main()
