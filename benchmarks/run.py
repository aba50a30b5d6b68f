"""Benchmark driver: one harness per paper table (+ the LM-stack micro
benches and the dry-run roofline summary), plus a **registry of optional
harnesses** discovered from the ``bench_*.py`` modules themselves.

Any ``benchmarks/bench_<name>.py`` that defines a module-level ``BENCH``
dict joins the registry with zero edits here::

    BENCH = {
        "name": "fit_matrix",                  # --bench fit_matrix
        "artifact": "BENCH_fit_matrix.json",   # results/ trajectory file
        "summary": ("n", "peak_mb"),           # axis/metric summary pair
        "quick": {...},                        # kwargs for run() (default)
        "full": lambda max_n: {...},           # kwargs for run() (--full)
    }

``--bench a,b`` runs the named harnesses after the core table suite;
``--bench all`` runs every discovered one; ``--list-benches`` prints the
registry; ``--bench a,b --gate`` runs them through the perf-regression
gate (benchmarks/gate.py) against the committed ``BENCH_*.json``
baselines instead — one command to run a registered bench and gate it. (This replaces the old hand-added ``--serve`` / ``--streaming``
/ ``--distributed`` flags — new executors get benchmarked by dropping in a
module, not by touching this driver.)

Output: `name,<row>` CSV per table on stdout (see each bench module's
header line). Harnesses that sweep an axis worth keeping record a
trajectory artifact under benchmarks/results/BENCH_<name>.json; this
driver prints a one-line summary per artifact at the end of every run,
using the registering module's ``summary`` hint when it has one. Schemas
are documented in docs/BENCHMARKS.md.
"""
from __future__ import annotations

import argparse
import functools
import glob as _glob
import importlib
import os
import sys

# make `python benchmarks/run.py` work from anywhere: the repo root (for the
# benchmarks package) and src/ (for repro) both go on sys.path
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_REPO, os.path.join(_REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import time


def discover_benches() -> dict:
    """name → registry spec for every bench_*.py exposing a ``BENCH`` dict.

    Discovery parses the source with ``ast`` instead of importing — bench
    modules pull in jax and the whole repro stack at module scope, which
    ``--summary-only`` / ``--list-benches`` must not pay for. Literal
    fields (``name``, ``artifact``, ``summary``) land in the spec; the
    module itself (for ``run()`` and the non-literal ``full`` lambda) is
    imported lazily by :func:`_run_registered` via the ``module_name``
    key."""
    import ast

    here = os.path.dirname(os.path.abspath(__file__))
    specs = {}
    for path in sorted(_glob.glob(os.path.join(here, "bench_*.py"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        try:
            tree = ast.parse(open(path).read())
        except SyntaxError:
            continue
        for node in tree.body:
            if not (isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "BENCH"
                    for t in node.targets)):
                continue
            if not isinstance(node.value, ast.Dict):
                continue
            spec = {"module_name": f"benchmarks.{stem}"}
            for k, v in zip(node.value.keys, node.value.values, strict=True):
                if isinstance(k, ast.Constant):
                    try:
                        spec[k.value] = ast.literal_eval(v)
                    except ValueError:  # lambdas etc.: import-time only
                        pass
            if "name" in spec:
                specs[spec["name"]] = spec
    return specs


def _lm_microbench(quick: bool = True):
    """LM-stack sanity perf: per-token train cost of smoke models."""
    import jax

    from benchmarks.common import print_csv, timed
    from repro.configs import ARCHS, SHAPES, smoke_config
    from repro.data import make_batch
    from repro.models import build
    from repro.train import OptConfig, init_opt_state, make_train_step

    rows = []
    for name in ("qwen2.5-32b", "mamba2-370m", "jamba-v0.1-52b"):
        cfg = smoke_config(ARCHS[name])
        bundle = build(cfg)
        params = bundle.init(jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        # repro: allow[RT303]: arch sweep — one compile per architecture is the intent; the wrapper is used immediately and discarded
        step = jax.jit(make_train_step(bundle, OptConfig()))
        batch = make_batch(cfg, SHAPES["train_4k"], 0, batch_override=4,
                           seq_override=64)
        (_, _, m), sec = timed(functools.partial(step, params, opt, batch),
                               warmup=1, iters=3)
        us_per_tok = sec / (4 * 64) * 1e6
        rows.append((name, "train_step", round(sec * 1e3, 2),
                     round(us_per_tok, 2)))
    print_csv("lm_microbench", rows, "arch,phase,ms_per_step,us_per_token")


def _kernel_microbench():
    """Clustering hot-spot timings (oracle path on CPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import print_csv, timed
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    rows = []
    x = jnp.asarray(rng.normal(size=(4096, 8)), jnp.float32)
    f = jax.jit(lambda a: ops.knn(a, 3, impl="ref"))
    _, sec = timed(f, x, warmup=1, iters=3)
    rows.append(("knn_4096x8_k3", round(sec * 1e3, 2),
                 round(sec / 4096 * 1e9, 1)))
    ids = jnp.asarray(rng.integers(0, 2048, size=4096), jnp.int32)
    g = jax.jit(lambda a, i: ops.segment_sum(a, i, 2048, impl="ref"))
    _, sec = timed(g, x, ids, warmup=1, iters=3)
    rows.append(("segment_sum_4096", round(sec * 1e3, 2),
                 round(sec / 4096 * 1e9, 1)))
    print_csv("kernel_microbench", rows, "kernel,ms,ns_per_point")


# fallback (axis, metric) pairs for artifacts whose writer predates the
# registry's per-module ``summary`` hint
_SUMMARY_AXES = (("devices", "seconds"), ("batch", "points_per_sec"),
                 ("n", "stream_peak_mb"), ("n", "peak_mb"))


def _bench_json_summary(specs: dict) -> None:
    """One summary line per benchmarks/results/BENCH_*.json trajectory.

    The sweep axis / metric pair comes from the registering module's
    ``summary`` hint when the artifact belongs to a registered harness,
    falling back to schema sniffing for anything else (docs/BENCHMARKS.md).
    """
    import json

    hints = {spec["artifact"]: spec.get("summary")
             for spec in specs.values() if spec.get("artifact")}
    results = os.path.join(os.path.dirname(__file__), "results")
    for path in sorted(_glob.glob(os.path.join(results, "BENCH_*.json"))):
        with open(path) as f:
            art = json.load(f)
        rows = art.get("rows", [])
        pair = hints.get(os.path.basename(path))
        if not (pair and rows and pair[0] in rows[0]):
            pair = next(
                (a for a in _SUMMARY_AXES if rows and a[0] in rows[0]),
                _SUMMARY_AXES[0])
        axis, metric = pair
        xs = ",".join(str(r.get(axis, "?")) for r in rows)
        ys = ",".join(str(r.get(metric, "?")) for r in rows)
        print(f"# {os.path.basename(path)}: {art.get('name')} "
              f"mode={art.get('mode')} {axis}=[{xs}] {metric}=[{ys}]")


def _run_registered(specs: dict, names, full: bool, max_n: int) -> None:
    for name in names:
        if name not in specs:
            print(f"# unknown bench {name!r}; have {sorted(specs)}",
                  file=sys.stderr)
            continue
        mod = importlib.import_module(specs[name]["module_name"])
        bench = getattr(mod, "BENCH", {})
        kwargs = bench.get("full") if full else bench.get("quick", {})
        if callable(kwargs):
            kwargs = kwargs(max_n)
        print(f"# bench {name}: {mod.__name__}.run("
              + ", ".join(f"{k}={v!r}" for k, v in (kwargs or {}).items())
              + ")")
        mod.run(**(kwargs or {}))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (hours on CPU)")
    ap.add_argument("--max-n", type=int, default=0)
    ap.add_argument("--bench", type=str, default="",
                    help="comma list of registered harnesses to run after "
                         "the core suite (or 'all'); see --list-benches")
    ap.add_argument("--gate", action="store_true",
                    help="run the named --bench harness(es) through the "
                         "perf-regression gate (benchmarks/gate.py) instead "
                         "of a plain run; skips the core table suite and "
                         "exits nonzero on a regression vs the committed "
                         "BENCH_*.json baselines")
    ap.add_argument("--gate-repeats", type=int, default=1,
                    help="with --gate: runs per harness (per-cell medians)")
    ap.add_argument("--gate-default-tol", type=float, default=None,
                    help="with --gate: one relative tolerance for every "
                         "metric (gate.py --default-tol)")
    ap.add_argument("--list-benches", action="store_true",
                    help="print the discovered bench registry and exit")
    ap.add_argument("--summary-only", action="store_true",
                    help="skip every harness; just print the one-line "
                         "summary per recorded BENCH_*.json artifact")
    args, _ = ap.parse_known_args()
    quick = not args.full

    specs = discover_benches()
    if args.list_benches:
        for name, spec in sorted(specs.items()):
            print(f"{name}: {spec['module_name']} "
                  f"(artifact {spec.get('artifact', '-')})")
        return
    if args.summary_only:
        _bench_json_summary(specs)
        return
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.gate:
        if not args.bench:
            ap.error("--gate needs --bench (which registered harnesses "
                     "to run and gate)")
        from benchmarks import gate

        names = (sorted(s for s in specs if specs[s].get("artifact"))
                 if args.bench.strip() == "all"
                 else [n.strip() for n in args.bench.split(",") if n.strip()])
        rc = 0
        for name in names:
            rc = max(rc, gate.gate_bench(
                name, full=args.full, max_n=args.max_n or 1_000_000,
                repeats=args.gate_repeats,
                default_tol=args.gate_default_tol))
        sys.exit(rc)

    from benchmarks import (bench_table1_kmeans, bench_table2_hac,
                            bench_table4_datasets, bench_table7_threshold,
                            bench_table9_dbscan)
    from benchmarks.common import PAPER_DATASETS

    t0 = time.time()
    if quick:
        bench_table1_kmeans.run(ns=(2_000, 20_000), ms=(0, 1, 2, 3))
        bench_table2_hac.run(ns=(4_000,), budget=512)
        bench_table4_datasets.run(max_n=20_000, ms=(0, 1, 2),
                                  datasets=PAPER_DATASETS[:3])
        bench_table7_threshold.run(n=5_000, ts=(2, 4, 8, 16))
        bench_table9_dbscan.run(max_n=4_000, ms=(1, 2))
        _lm_microbench()
        _kernel_microbench()
    else:
        mx = args.max_n or 1_000_000
        bench_table1_kmeans.run(
            ns=tuple(n for n in (10_000, 100_000, 1_000_000) if n <= mx))
        bench_table2_hac.run(
            ns=tuple(n for n in (10_000, 100_000, 1_000_000) if n <= mx))
        bench_table4_datasets.run(max_n=min(mx, 600_000))
        bench_table7_threshold.run(n=min(mx, 100_000))
        bench_table9_dbscan.run(max_n=min(mx, 50_000))
        _lm_microbench()
        _kernel_microbench()

    if args.bench:
        names = (sorted(specs) if args.bench.strip() == "all"
                 else [n.strip() for n in args.bench.split(",") if n.strip()])
        _run_registered(specs, names, args.full,
                        args.max_n or 1_000_000)

    # dry-run roofline summary, if artifacts exist
    results = os.path.join(os.path.dirname(__file__), "results", "dryrun")
    if os.path.isdir(results) and os.listdir(results):
        from benchmarks import roofline

        cells = roofline.load(results)
        ok = sum(1 for c in cells if c["status"] == "ok")
        skip = sum(1 for c in cells if c["status"] == "skip")
        err = sum(1 for c in cells if c["status"] not in ("ok", "skip"))
        print(f"# dryrun_cells: ok={ok} skip={skip} error={err}")
    _bench_json_summary(specs)
    print(f"# total_bench_seconds,{round(time.time() - t0, 1)}")


if __name__ == "__main__":
    main()
