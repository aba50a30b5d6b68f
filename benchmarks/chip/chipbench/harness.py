"""Run one cell: set up, measure, trace, check, and build the result line.

The runner is chosen by the traffic file's ``loop``; there is one:

``fit``   whole fits back to back through ``repro.fit`` on the memory
          executor. Set-up draws the data and runs one full fit, which
          compiles (or loads) every program a fit uses. The window ends
          when the fit that crosses ``seconds`` completes; ``fit_s`` is the
          window over the fits completed in it. ``--trace 1`` traces one fit
          and records its level and k-means program calls; after the
          window, each call's compiled text gives the trace its scope
          paths (:mod:`chipbench.scopes`), and the run fails where the
          calls recorded are not the programs' runs in the trace.

Every number compared by ``correct`` comes from :mod:`chipbench.checks`,
after the window has closed and the peak memory has been read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from chipbench import checks, data, reference, scopes, work
from chipbench import trace as trace_mod
from chipbench.catalog import BENCH_DIR, Cell

class Counters:
    """Compilations and persistent-cache reads, from JAX's monitoring
    events; ``window`` counts only those inside the measured window."""

    def __init__(self):
        self.total = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
        self.window = dict(self.total)
        self.in_window = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _bump(self, name: str) -> None:
        self.total[name] += 1
        if self.in_window:
            self.window[name] += 1

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._bump("compiles")

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._bump("cache_hits")
        elif event == "/jax/compilation_cache/cache_misses":
            self._bump("cache_misses")


@dataclasses.dataclass
class Outcome:
    """What a run measured, before it becomes the result line."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: Dict[str, dict]
    ctx: "ReadCtx"
    info: Dict[str, Any]


@dataclasses.dataclass
class ReadCtx:
    """What a per-layer reader (``metrics/<name>.py``) may read."""

    loop: str
    trace: Optional[trace_mod.Trace] = None
    peaks: Optional[work.Peaks] = None
    d: int = 0
    knn_k: int = 0
    level_valid: List[int] = dataclasses.field(default_factory=list)
    #: the backend's k, and the valid prototypes it clusters
    k: int = 0
    proto_valid: int = 0
    fits: int = 0
    #: the traced fit's ``FitResult.counters``, on the host
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _now() -> float:
    return time.perf_counter()


def _log(msg: str) -> None:
    print(msg, flush=True)


def _block_fit(res) -> None:
    jax.block_until_ready((res.labels, res.protos, res.proto_labels,
                           res.proto_mass, res.proto_valid))


def _fit_fn(x, fit_cfg: dict):
    import repro

    def run(key):
        res = repro.fit(x, int(fit_cfg["t"]), int(fit_cfg["m"]),
                        fit_cfg["backend"], k=int(fit_cfg["k"]), key=key,
                        executor=fit_cfg["executor"],
                        weighted=bool(fit_cfg.get("weighted", False)))
        _block_fit(res)
        return res

    return run


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _trace_dir(cell: Cell, seed: int) -> str:
    return str(BENCH_DIR / ".traces" / f"{cell.name}-{seed}")


@contextlib.contextmanager
def _traced(enabled: bool, path: str):
    if not enabled:
        yield
        return
    jax.profiler.start_trace(path)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()


def _span(enabled: bool, name: str):
    return (jax.profiler.TraceAnnotation(name) if enabled
            else contextlib.nullcontext())


# ---------------------------------------------------------------------------
# fit cells


def run_fit(cell: Cell, seed: int, seconds: float, tracing: bool,
            counters: Counters, t_start: float) -> Outcome:
    cfg, fc = cell.config, cell.config["fit"]
    kdata, kwarm, kfit = jax.random.split(data.seed_key(seed), 3)
    x = data.draw(cfg, kdata)
    fit = _fit_fn(x, fc)
    fit(kwarm)  # set-up: every program of the fit compiles or loads here
    setup_s = _now() - t_start
    _log(f"setup: seconds={setup_s} compile={counters.total}")

    path = _trace_dir(cell, seed)
    calls: list = []
    recording = (scopes.recording(calls) if tracing
                 else contextlib.nullcontext())
    counters.in_window = True
    fits, t0, ends = 0, _now(), []
    with recording, _traced(tracing, path):
        while True:
            with _span(tracing, "bench.fit"):
                res = fit(jax.random.fold_in(kfit, fits))
            fits += 1
            ends.append(_now())
            if tracing or ends[-1] - t0 >= seconds:
                break
    window_s = _now() - t0
    counters.in_window = False
    peak = _peak_bytes(jax.devices()[0])
    each = np.diff([t0] + ends).tolist()
    _log(f"window: fits={fits} seconds={window_s} each={each} "
         f"compiles_in_window={counters.window}")
    texts = scopes.compiled_texts(calls)  # no calls unless tracing
    calls.clear()

    limits = checks.limits_for(cfg, "fit")
    numbers = checks.fit_numbers(x, res, fc, data.host_rng(seed, 1),
                                 knn="knn_gap" in limits)
    ok, chk = checks.verdict(numbers, limits)
    e2e = {"setup_s": setup_s, "fit_s": window_s / fits,
           "fit_bss_tss": reference.bss_tss(x, res.labels, int(fc["k"]))}
    ctx = ReadCtx(loop="fit", d=int(cfg["d"]), knn_k=int(fc["t"]) - 1,
                  level_valid=[int((np.asarray(a) >= 0).sum())
                               for a in res.assignments], k=int(fc["k"]),
                  proto_valid=int(np.asarray(res.proto_valid).sum()),
                  fits=fits)
    if tracing:
        ctx.counters = jax.device_get(getattr(res, "counters", {}))
        ctx.trace = scopes.load(path, remove=True)
        scopes.attach_recorded(ctx.trace, texts)
    return Outcome(ok, fits, 0, e2e, chk, ctx,
                   {"memory_peak_bytes": peak, "window_s": window_s})


RUNNERS = {"fit": run_fit}


# ---------------------------------------------------------------------------
# the result line


def result_line(cell: Cell, out: Outcome, tracing: bool,
                rehearse: bool = False) -> Dict[str, Any]:
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": out.info["memory_peak_bytes"]}
    metrics = {}
    if tracing:
        # a rehearsal's CPU has no peaks: its rooflines stay unread
        out.ctx.peaks = None if rehearse else work.peaks(dev.device_kind)
        for m in cell.per_layer:
            value = cell.readers[m["name"]](out.ctx)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        tr = out.ctx.trace
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(out.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    line = {"correct": bool(out.correct), "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": device}
    if tracing:
        line["breakdown"] = {"device_ops": out.ctx.trace.top_ops(10),
                             "idle_gaps": out.ctx.trace.idle_gaps(10)}
    line["checks"] = out.checks
    return line


def run_cell(cell: Cell, seed: int, seconds: float, tracing: bool,
             t_start: float, counters: Optional[Counters] = None) -> Outcome:
    """Run ``cell`` on whatever backend JAX has (the caller checks it)."""
    counters = counters or Counters()
    return RUNNERS[cell.loop](cell, seed, seconds, tracing, counters, t_start)


def resized(cell: Cell, n: Optional[int]) -> Cell:
    """The cell at ``n`` points, for a rehearsal on the CPU (never for a
    measurement)."""
    if n is None:
        return cell
    return dataclasses.replace(cell, config=dict(cell.config, n=int(n)))
