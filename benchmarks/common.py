"""Shared benchmark utilities: the paper's GMM generator, synthetic analogs
of the six real datasets (the container is offline), timing and working-set
measurement, and the one-line-per-row CSV emitter every harness uses.

Conventions (docs/BENCHMARKS.md):
  * every harness prints exactly one ``# <name>: <header>`` line followed by
    ``<name>,<row>`` CSV lines — grep a name to extract one table;
  * timings come from :func:`timed` (jit warmup excluded, device sync
    included); memory is :func:`live_mb` (live device buffers, the analog of
    the paper's R memory profiling);
  * sweeps worth keeping across runs are also written as JSON artifacts to
    benchmarks/results/ (``BENCH_*.json`` for benchmark trajectories, as in
    bench_distributed; tagged per-cell files under results/hillclimb and
    results/dryrun for the LM stack).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Tuple

import jax
import numpy as np


def gmm_sample(n: int, seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Paper §4: mixture of three bivariate Gaussians (.5/.3/.2)."""
    rng = np.random.default_rng(seed)
    mus = np.array([[1, 2], [7, 8], [3, 5]], float)
    sds = np.array([[1, 0.5], [2, 1], [3, 4]], float) ** 0.5
    comp = rng.choice(3, size=n, p=[0.5, 0.3, 0.2])
    x = mus[comp] + rng.normal(size=(n, 2)) * sds[comp]
    return x.astype(np.float32), comp


@dataclass(frozen=True)
class DatasetSpec:
    """Shape of one of the paper's Table-3 real datasets: n rows, d numeric
    features, k clusters requested in the paper's experiments."""
    name: str
    n: int
    d: int
    k: int


# Table 3 of the paper; data drawn as a k-component Gaussian mixture with the
# matching (n, d, k) since the container has no network access. The paper's
# claims under test (runtime/memory vs m, BSS/TSS preservation) depend on
# scale and cluster structure, not on the exact real-world marginals.
PAPER_DATASETS = [
    DatasetSpec("pm25", 41_757, 5, 4),
    DatasetSpec("credit_score", 120_269, 6, 5),
    DatasetSpec("black_friday", 166_986, 7, 4),
    DatasetSpec("covertype", 581_012, 6, 7),
    DatasetSpec("house_price", 2_885_485, 5, 5),
    DatasetSpec("stock", 7_026_593, 5, 7),
]


def dataset_analog(spec: DatasetSpec, seed: int = 0, max_n: int = 0) -> np.ndarray:
    """Synthetic stand-in for a Table-3 dataset: a k-component Gaussian
    mixture with the spec's (n, d, k); ``max_n`` truncates for quick mode."""
    n = min(spec.n, max_n) if max_n else spec.n
    rng = np.random.default_rng(seed)
    centers = rng.normal(scale=4.0, size=(spec.k, spec.d))
    comp = rng.integers(0, spec.k, size=n)
    scales = rng.uniform(0.5, 1.5, size=(spec.k, spec.d))
    x = centers[comp] + rng.normal(size=(n, spec.d)) * scales[comp]
    return x.astype(np.float32)


def live_mb() -> float:
    """Current live device-buffer footprint in MB (the working-set metric —
    the analog of the paper's R memory profiling)."""
    return sum(a.nbytes for a in jax.live_arrays()) / 1e6


def timed(fn: Callable, *args, warmup: int = 1, iters: int = 1, **kw):
    """(result, seconds) with jit warmup excluded and device sync included."""
    out = None
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(jax.tree_util.tree_leaves(out))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
        jax.block_until_ready(jax.tree_util.tree_leaves(out))
    return out, (time.perf_counter() - t0) / iters


def require_forced_cpu_devices(devices: int) -> None:
    """Guard for the children of the forced-host-device launchers
    (bench_fit_matrix, bench_distributed): ``--xla_force_host_platform_
    device_count`` only makes devices on the CPU backend, so on any other
    backend the child refuses (exit 2) instead of measuring something else.
    The launchers pass the caller's ``JAX_PLATFORMS`` through unchanged and
    never force the CPU on a machine that has a chip."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise SystemExit(
            f"forced host devices exist only on the CPU backend, but JAX "
            f"chose {backend!r}; run with JAX_PLATFORMS=cpu to measure the "
            f"CPU simulation")
    assert len(jax.devices()) == devices, (len(jax.devices()), devices)


def run_child(argv: list, env: dict, cwd: str, timeout: int) -> str:
    """Run one launcher child; its failure ends the launcher (non-zero)."""
    import subprocess
    import sys

    proc = subprocess.run(argv, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=cwd)
    if proc.returncode != 0:
        print(proc.stderr, file=sys.stderr)
        raise SystemExit(f"{' '.join(argv[2:4])} failed with exit code "
                         f"{proc.returncode}")
    return proc.stdout


def print_csv(name: str, rows: list, header: str) -> None:
    """Emit one benchmark table: a ``# name: header`` comment line, then one
    ``name,<row>`` line per row (grep the name to extract the table)."""
    print(f"# {name}: {header}")
    for r in rows:
        print(f"{name}," + ",".join(str(x) for x in r))
