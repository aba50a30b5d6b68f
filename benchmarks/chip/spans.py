#!/usr/bin/env python3
"""Where a fit's device time goes, by the program's own spans, scopes and
counters, and what tracing costs.

    python3 benchmarks/chip/spans.py --workload sift1m.fit --seed 7 \\
        [--untraced 2] [--out FILE]

One process sets the cell up as a run does (data from ``--seed``, the
programs loaded from ``<checkout>/.jax_cache``, one warm-up fit), times
``--untraced`` fits with the profiler off, then traces one fit and prints
one JSON line: device seconds per scope of the level program (``tc.knn``,
``tc.mis``, ``tc.grow``, ``itis.reduce``) and of the k-means program
(``kmeans.seed``), their share of the level program's time, device seconds
per ITIS level, the fit's counters (``mis_rounds``, ``lloyd_iters``), the
idle time under the innermost host span, the traced fit's window against
the untraced fits' wall times, and how many operations found their scope
path.

A TPU trace carries no scope path (``chipbench/scopes.py``). The warm-up
fit records each call of the level and k-means programs; after it, the
optimized HLO text of each call's executable (``lower(...).compile()
.as_text()``, loaded from the compile cache as the fit loaded it) maps
every instruction to its ``op_name``: the same map as the optimized HLO
that ``--xla_dump_to`` writes when the programs compile, instruction for
instruction (PERF.md section 3).

The benchmark's runs never run this. ``--rehearse`` runs it on the CPU
at ``--n`` points (Pallas interpret mode) and exits 3: the CPU trace has
host spans but no device operations.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

REHEARSED = 3


def measure(cell, seed: int, untraced: int, path: str) -> dict:
    import jax
    import numpy as np

    from chipbench import data, harness, scopes

    cfg, fc = cell.config, cell.config["fit"]
    kdata, kwarm, kfit = jax.random.split(data.seed_key(seed), 3)
    x = data.draw(cfg, kdata)
    fit = harness._fit_fn(x, fc)
    calls: list = []
    with scopes.recording(calls):
        fit(kwarm)
    t0 = time.perf_counter()
    names = scopes.by_module(scopes.compiled_texts(calls))
    names_s = time.perf_counter() - t0
    walls = []
    for i in range(untraced):
        t0 = time.perf_counter()
        fit(jax.random.fold_in(kfit, i))
        walls.append(time.perf_counter() - t0)
    with harness._traced(True, path):
        with harness._span(True, "bench.fit"):
            res = fit(jax.random.fold_in(kfit, untraced))
    tr = scopes.load(path, remove=True)
    tr.attach_paths(names)

    itis = tr.op_seconds(module=scopes.LEVEL_PROGRAM)
    per_scope = {s: tr.scope_seconds(s, module=scopes.LEVEL_PROGRAM)
                 for s in scopes.ITIS_SCOPES}
    per_scope.update({s: tr.scope_seconds(s, module="_kmeans")
                      for s in scopes.BACKEND_SCOPES})
    unscoped = {}
    for e in tr.ops:
        if e.leaf and "_itis_step" in e.group and not any(
                scopes.in_scope(tr.paths.get(e, ""), s)
                for s in scopes.ITIS_SCOPES):
            key = f"{e.name.split('.')[0]} [{tr.paths.get(e, '')}]"
            unscoped[key] = unscoped.get(key, 0) + (e.end - e.start) / 1e9
    counters = {k: np.asarray(v).tolist()
                for k, v in jax.device_get(res.counters).items()}
    return {
        "cell": cell.name, "seed": seed,
        "device": jax.devices()[0].device_kind,
        "untraced_fit_s": walls, "window_s": tr.window_s(),
        "tracing_overhead": (tr.window_s() / statistics.median(walls) - 1
                             if walls else None),
        "busy_s": tr.busy_s(),
        "itis_device_s": itis,
        "kmeans_device_s": tr.op_seconds(module="_kmeans"),
        "scope_s": per_scope,
        "itis_scope_share": (sum(per_scope[s] for s in scopes.ITIS_SCOPES)
                             / itis if itis > 0 else None),
        "unscoped_itis_ops": sorted(unscoped.items(),
                                    key=lambda kv: -kv[1])[:10],
        "level_s": scopes.level_seconds(tr),
        "level_spans": sum(e.name == scopes.LEVEL_SPAN for e in tr.host),
        "level_valid": [int((np.asarray(a) >= 0).sum())
                        for a in res.assignments],
        "level_rows": [int(np.asarray(a).shape[0]) for a in res.assignments],
        "counters": counters,
        "idle_gaps": tr.idle_gaps(10), "idle_by_span": tr.idle_by_span(),
        "top_ops": tr.top_ops(12),
        "leaf_ops": sum(e.leaf for e in tr.ops),
        "leaf_ops_with_path": sum(e.leaf and e in tr.paths for e in tr.ops),
        "paths_s": names_s,
        "modules_with_paths": {k: len(v) for k, v in names.items()},
        "scopes_in_paths": sorted(
            s for s in scopes.ITIS_SCOPES + scopes.BACKEND_SCOPES
            if any(scopes.in_scope(p, s) for mods in names.values()
                   for m in mods for p in m.values())),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--untraced", type=int, default=2,
                    help="fits timed with the profiler off")
    ap.add_argument("--out", default=None, help="also append the line here")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args()

    from chipbench.catalog import use_checkout_cache

    if not args.rehearse:
        use_checkout_cache()
    import jax

    from chipbench import harness
    from chipbench.catalog import BENCH_DIR, Catalog
    from repro import runtime
    from repro.runtime.compile_cache import enable_compile_cache

    if not args.rehearse and jax.default_backend() != "tpu":
        print("spans.py: no TPU", file=sys.stderr)
        sys.exit(2)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    cell = Catalog().cell(args.workload)
    scope = {"tune": "off"}
    if args.rehearse:
        scope.update(impl="pallas", interpret=True)
        cell = harness.resized(cell, args.n)
    path = str(BENCH_DIR / ".traces" / f"spans-{cell.name}-{args.seed}")
    with runtime.configure(**scope):
        line = json.dumps(measure(cell, args.seed, args.untraced, path,
))
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    if args.rehearse:
        sys.exit(REHEARSED)


if __name__ == "__main__":
    main()
