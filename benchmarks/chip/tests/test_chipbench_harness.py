"""The harness end to end on the CPU: the rehearsal prints no result line,
a run without a TPU exits non-zero, and a run whose timed path is broken
underneath, or runs one precision step down, comes out not correct."""
import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import harness, lower  # noqa: E402
from chipbench.catalog import BENCH_DIR, ROOT, Catalog  # noqa: E402

RUN = str(BENCH_DIR / "run.py")
N = 4096          # points of a rehearsal
SEED = 2_500_000_017


def _run(*args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, RUN, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            out.append(obj)
    return out


def test_without_a_tpu_the_run_exits_non_zero_and_prints_no_result():
    p = _run("--workload", "sift1m.fit", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode == 2, p.stderr
    assert not _result_lines(p.stdout)
    assert "not a TPU" in p.stderr


@pytest.mark.parametrize("workload", ["covertype.fit", "sift1m.fit"])
def test_rehearsal_runs_every_phase_and_prints_no_result(workload):
    p = _run("--workload", workload, "--seed", str(SEED), "--seconds", "1",
             "--trace", "0", "--rehearse", "--n", str(N))
    assert p.returncode == 3, p.stdout[-2000:] + p.stderr[-2000:]
    assert not _result_lines(p.stdout)
    assert "rehearsal: correct=True" in p.stdout
    assert "in_window={'compiles': 0" in p.stdout
    assert p.stderr.strip().splitlines()[-1].startswith("check ")


def test_a_directory_with_only_the_benchmark_fails(tmp_path):
    """Without the program beside it the benchmark cannot run: it exits
    non-zero with no result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".traces"))
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "sift1m.fit", "--seed", "1",
                        "--seconds", "1", "--rehearse", "--n", "256"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode not in (0, 3)
    assert not _result_lines(p.stdout)


# ---------------------------------------------------------------------------
# faults planted underneath the timed path


def _rehearse(workload, seconds=1.0, **data_overrides):
    import dataclasses

    from repro import runtime

    cell = harness.resized(Catalog().cell(workload), N)
    if data_overrides:
        cfg = dict(cell.config,
                   data=dict(cell.config["data"], **data_overrides))
        cell = dataclasses.replace(cell, config=cfg)
    jax.clear_caches()
    with runtime.configure(impl="pallas", interpret=True, tune="off"):
        out = harness.run_cell(cell, SEED, seconds, False,
                               harness._now(), harness.Counters())
    jax.clear_caches()
    return out


def test_untraced_run_records_no_program_call(monkeypatch):
    """Scope paths and counters are for the traced run alone: an untraced
    run records no call and fetches no counter."""
    from chipbench import scopes

    def refuse(*_):
        raise AssertionError("scope work in an untraced run")

    monkeypatch.setattr(scopes, "recording", refuse)
    monkeypatch.setattr(scopes, "attach_recorded", refuse)
    out = _rehearse("covertype.fit")
    assert out.correct, out.checks
    assert out.ctx.trace is None
    assert out.ctx.counters == {}
    assert out.ctx.k == 7
    assert 0 < out.ctx.proto_valid <= N // 2 ** 4


def test_traced_run_reads_scope_paths_and_counters(monkeypatch):
    """The traced fit's level and k-means calls are recorded and their
    compiled texts read after the window; the trace keeps the program's
    host spans, and the counter metrics reach the result line. (A CPU
    trace has no device operations, so no scope time is read here.)"""
    from chipbench import scopes
    from repro import runtime

    texts = []
    orig = scopes.compiled_texts

    def spy(calls):
        texts.extend(orig(calls))
        return texts

    monkeypatch.setattr(scopes, "compiled_texts", spy)
    cell = harness.resized(Catalog().cell("covertype.fit"), N)
    jax.clear_caches()
    with runtime.configure(impl="pallas", interpret=True, tune="off"):
        out = harness.run_cell(cell, SEED, 1.0, True, harness._now(),
                               harness.Counters())
    jax.clear_caches()
    assert out.correct, out.checks
    assert out.attempted == 1
    modules = scopes.by_module(texts)
    assert {k: len(v) for k, v in modules.items()} == {"jit__itis_step": 4,
                                                       "jit__kmeans": 1}
    assert isinstance(out.ctx.trace, scopes.ScopedTrace)
    assert len(out.ctx.counters["mis_rounds"]) == 4
    assert set(out.ctx.counters) == {"mis_rounds", "reduce_tiles",
                                     "lloyd_iters"}
    names = {e.name for e in out.ctx.trace.host}
    assert {"bench.fit", "repro.fit", "repro.itis.level"} <= names
    line = harness.result_line(cell, out, True, rehearse=True)
    for name in ("mis_rounds.fit", "reduce_tiles.fit"):
        assert line["metrics"][name]["unit"] == "count"
        assert line["metrics"][name]["value"] >= 1
    assert "lloyd_iters.fit" not in line["metrics"]  # sift1m's alone
    assert "knn_device_s.fit" not in line["metrics"]


def _failing(out):
    return sorted(k for k, c in out.checks.items() if c["value"] > c["limit"])


def test_sound_fit_is_correct():
    out = _rehearse("covertype.fit")
    assert out.correct, out.checks


def test_fit_prototype_mean_over_half_its_members(monkeypatch):
    itis = importlib.import_module("repro.core.itis")
    orig = itis.reduce_to_prototypes

    def half(x, labels, n_max, **kw):
        full = orig(x, labels, n_max, **kw)
        keep = jnp.arange(labels.shape[0]) % 2 == 0
        part = orig(x, jnp.where(keep, labels, -1), n_max, **kw)
        return full._replace(x=jnp.where(part.valid[:, None], part.x, full.x))

    monkeypatch.setattr(itis, "reduce_to_prototypes", half)
    out = _rehearse("covertype.fit")
    assert not out.correct
    assert "proto_err" in _failing(out)


def test_fit_lloyd_step_returns_its_state_unchanged(monkeypatch):
    """No Lloyd step runs: the labels stay those of the seeding. On
    well-separated components the seeding's labels are already the fixed
    point, so the components here overlap."""
    km = importlib.import_module("repro.cluster.kmeans")
    orig = km.kmeans
    assert _rehearse("covertype.fit", center_scale=0.5).correct
    monkeypatch.setattr(km, "kmeans",
                        lambda *a, **kw: orig(*a, **dict(kw, iters=0)))
    out = _rehearse("covertype.fit", center_scale=0.5)
    assert not out.correct
    assert "kmeans_moves" in _failing(out)


def test_fit_label_altered_where_it_is_produced(monkeypatch):
    import repro

    orig = repro.fit

    def altered(*a, **kw):
        res = orig(*a, **kw)
        lab = res.labels
        res._labels = lab.at[7].set((lab[7] + 1) % int(kw["k"]))
        return res

    monkeypatch.setattr(repro, "fit", altered)
    out = _rehearse("covertype.fit")
    assert not out.correct
    assert _failing(out) == ["backout"]


@pytest.mark.parametrize("workload", ["covertype.fit", "sift1m.fit"])
def test_fit_one_precision_step_down_is_not_correct(workload):
    """The control: the program's own kernels with their float32 matmuls
    taken at Precision.HIGH, through the harness's own comparison."""
    with lower.high_matmuls():
        out = _rehearse(workload)
    assert not out.correct
    assert "proto_err" in _failing(out)
