"""The program's spans in a trace: device time by scope path, device time
per ITIS level matched to the level spans or to the fit's level count
(the ``itis_level0_device_s.fit`` reader), idle gaps named by the
innermost ``repro.*`` span, a CPU trace of a real fit, the spans
script's rehearsal, and the per-layer readers of scopes and counters."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "src"))

from chipbench import scopes  # noqa: E402
from chipbench.catalog import BENCH_DIR, ROOT, Catalog  # noqa: E402
from chipbench.harness import ReadCtx  # noqa: E402
from chipbench.trace import Event, from_events, mark_leaves  # noqa: E402

MS = 1_000_000  # ns
STEP = "jit(_itis_step)/jit(_threshold_clustering)"


def _op(name, s, t, group, path):
    return Event(name, s * MS, t * MS, group), path


def scoped(ops, modules, host):
    """A trace of ``(event, scope path)`` pairs, the paths kept aside."""
    path_of = {(e.name, e.start): p for e, p in ops}
    tr = from_events(mark_leaves([e for e, _ in ops]), modules, host)
    return scopes.ScopedTrace(
        tr.ops, tr.modules, tr.host, tr.window, tr.chips,
        paths={e: path_of[e.name, e.start] for e in tr.ops
               if path_of[e.name, e.start]})


def synthetic(levels_in_second_fit=2):
    """Two fits in a 0..200 ms window. Fit 1 (0..90): level programs at
    10..40 and 50..60, then k-means at 70..80. Fit 2 (100..190): its level
    spans and programs as ``levels_in_second_fit`` says."""
    host = [Event("bench.window", 0, 200 * MS),
            Event("bench.fit", 0, 90 * MS), Event("repro.fit", 0, 89 * MS),
            Event("repro.itis.level", 1 * MS, 2 * MS),
            Event("repro.itis.sync", 2 * MS, 41 * MS),
            Event("repro.itis.level", 41 * MS, 49 * MS),
            Event("repro.fit.finalize", 60 * MS, 89 * MS),
            Event("bench.fit", 100 * MS, 190 * MS),
            Event("repro.itis.level", 101 * MS, 102 * MS),
            Event("repro.itis.level", 131 * MS, 132 * MS)]
    modules = [Event("jit__itis_step", 10 * MS, 40 * MS, "0"),
               Event("jit__itis_step", 50 * MS, 60 * MS, "0"),
               Event("jit__kmeans", 70 * MS, 80 * MS, "0"),
               Event("jit__itis_step", 110 * MS, 130 * MS, "0")]
    ops = [
        _op("while.2", 10, 25, "jit__itis_step", f"{STEP}/tc.mis/while"),
        _op("_fused_topk.3", 10, 20, "jit__itis_step",
            f"{STEP}/tc.knn/jit(knn_graph_blocked)/pallas_call"),
        _op("fusion.1", 20, 25, "jit__itis_step", f"{STEP}/tc.mis/while/body"),
        _op("fusion.4", 25, 28, "jit__itis_step", f"{STEP}/tc.grow/scatter"),
        _op("segment_sum.2", 28, 39, "jit__itis_step",
            "jit(_itis_step)/itis.reduce/jit(_reduce_to_prototypes)/pc"),
        _op("copy.1", 39, 40, "jit__itis_step", ""),
        _op("_fused_topk.3", 50, 56, "jit__itis_step",
            f"{STEP}/tc.knn/pallas_call"),
        _op("segment_sum.2", 56, 60, "jit__itis_step",
            "jit(_itis_step)/itis.reduce/pc"),
        _op("pairwise_sq_l2.1", 70, 72, "jit__kmeans",
            "jit(_kmeans)/kmeans.seed/while/body/pallas_call"),
        _op("pairwise_sq_l2.1", 72, 80, "jit__kmeans",
            "jit(_kmeans)/while/body/pallas_call"),
        _op("_fused_topk.3", 110, 125, "jit__itis_step",
            f"{STEP}/tc.knn.extra/pallas_call"),
        _op("segment_sum.2", 125, 130, "jit__itis_step",
            "jit(_itis_step)/itis.reduce/pc"),
    ]
    if levels_in_second_fit == 2:
        modules.append(Event("jit__itis_step", 140 * MS, 150 * MS, "0"))
        ops.append(_op("_fused_topk.3", 140, 150, "jit__itis_step",
                       f"{STEP}/tc.knn/pallas_call"))
    return scoped(ops, modules, host)


def test_scope_seconds_sum_leaves_whose_path_has_the_scope():
    tr = synthetic()
    # the while loop is not a leaf; "tc.knn.extra" is not "tc.knn"
    assert tr.scope_seconds("tc.knn") == pytest.approx(0.026)
    assert tr.scope_seconds("tc.mis") == pytest.approx(0.005)
    assert tr.scope_seconds("tc.grow") == pytest.approx(0.003)
    assert tr.scope_seconds("itis.reduce") == pytest.approx(0.020)
    assert tr.scope_seconds("kmeans.seed", module="_kmeans") == \
        pytest.approx(0.002)
    assert tr.scope_seconds("kmeans.seed", module="_itis_step") == 0.0
    assert tr.scope_seconds("tc") == 0.0
    # the unscoped copy and the odd "tc.knn.extra" stay outside the four
    itis = tr.op_seconds(module="_itis_step")
    four = sum(tr.scope_seconds(s, module="_itis_step")
               for s in scopes.ITIS_SCOPES)
    assert itis - four == pytest.approx(0.016)


def test_level_seconds_match_programs_to_level_spans_per_fit():
    tr = synthetic()
    # fit 1: level 0 = 10+5+3+11+1 ms, level 1 = 6+4; fit 2: 15+5, 10
    assert scopes.level_seconds(tr) == pytest.approx([0.025, 0.010])
    assert scopes.level_seconds(tr, levels=2) == pytest.approx([0.025, 0.010])


def test_level_seconds_are_none_when_the_counts_differ():
    assert scopes.level_seconds(synthetic(levels_in_second_fit=1)) is None
    assert scopes.level_seconds(synthetic(), levels=3) is None
    assert scopes.level_seconds(synthetic(), levels=0) is None
    empty = from_events([], [], [Event("bench.fit", 0, 1)])
    assert scopes.level_seconds(empty) is None


def _as_the_harness_loads_it(tr):
    """The trace as ``chipbench.trace.load`` keeps it: no scope paths, and
    only the benchmark's own host spans."""
    return from_events(tr.ops, tr.modules,
                       [e for e in tr.host if e.name.startswith("bench.")])


@pytest.fixture(scope="module")
def level0_reader():
    return Catalog().reader("itis_level0_device_s.fit")


def test_level0_reader_reads_each_fits_first_level_program(level0_reader):
    ctx = ReadCtx(loop="fit", trace=_as_the_harness_loads_it(synthetic()),
                  level_valid=[600, 200], fits=2)
    assert level0_reader(ctx) == pytest.approx(0.025)


@pytest.mark.parametrize("case", ["more_maps", "one_fit_short", "no_trace",
                                  "no_maps"])
def test_level0_reader_reads_nothing_when_the_counts_differ(level0_reader,
                                                            case):
    tr = _as_the_harness_loads_it(
        synthetic(levels_in_second_fit=1 if case == "one_fit_short" else 2))
    ctx = ReadCtx(loop="fit", trace=None if case == "no_trace" else tr,
                  level_valid={"more_maps": [600, 200, 90],
                               "no_maps": []}.get(case, [600, 200]), fits=2)
    assert level0_reader(ctx) is None


def test_level0_reader_is_a_metric_of_both_fit_cells():
    for name in ("sift1m.fit", "covertype.fit"):
        cell = Catalog().cell(name)
        assert "itis_level0_device_s.fit" in cell.readers


def test_idle_gaps_fall_under_the_innermost_program_span():
    named = [[n, round(g, 6)] for n, g in synthetic().idle_gaps(20)]
    # 0..10 ms: repro.fit covers it whole, as bench.fit does, and is
    # shorter; 60..70 ms: the finalize span covers it whole
    assert ["repro.fit", 0.010] in named
    assert ["repro.fit.finalize", 0.010] in named
    assert all(n != "bench.window" for n, _ in named)


def test_idle_seconds_by_the_shortest_running_span():
    by = synthetic().idle_by_span()
    want = {"repro.fit": 0.002, "repro.itis.level": 0.011,
            "repro.itis.sync": 0.009, "repro.fit.finalize": 0.019,
            "bench.fit": 0.059, "untraced host work": 0.020}
    assert by == pytest.approx(want)
    tr = synthetic()
    assert sum(by.values()) == pytest.approx(tr.window_s() - tr.busy_s())


def test_a_cpu_trace_of_a_fit_has_one_level_span_per_level_run(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(512, 4)).astype(np.float32))
    repro.fit(x, 2, 3, "kmeans", k=4)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            res = repro.fit(x, 2, 3, "kmeans", k=4)
            jax.block_until_ready(res.labels)
    tr = scopes.load(str(tmp_path))
    names = [e.name for e in tr.host]
    assert names.count("repro.itis.level") == len(res.assignments) == 3
    assert names.count("repro.itis.sync") == 3
    for span in ("repro.fit", "repro.fit.plan", "repro.fit.finalize"):
        assert names.count(span) == 1, span
    fit = next(e for e in tr.host if e.name == "repro.fit")
    assert all(fit.start <= e.start and e.end <= fit.end for e in tr.host
               if e.name.startswith("repro."))


def test_spans_rehearsal_prints_counters_and_spans():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH_DIR / "spans.py"), "--workload",
         "covertype.fit", "--seed", "2500000017", "--untraced", "1",
         "--rehearse", "--n", "2048"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 3, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["level_spans"] == len(line["level_valid"]) == 4
    assert len(line["counters"]["mis_rounds"]) == 4
    assert min(line["counters"]["mis_rounds"]) >= 1
    assert line["counters"]["lloyd_iters"] >= 1
    # the executables' own text maps every scope, one module a level run
    assert line["modules_with_paths"] == {"jit__itis_step": 4,
                                          "jit__kmeans": 1}
    assert line["scopes_in_paths"] == sorted(scopes.ITIS_SCOPES
                                             + scopes.BACKEND_SCOPES)


HLO = """HloModule jit__itis_step, is_scheduled=true

ENTRY %main.65 (x.1: f32[8,6]) -> f32[4,6] {
  %_fused_topk.6 = (f32[8,1]) custom-call(%x.1), metadata={op_name="jit(_itis_step)/jit(_threshold_clustering)/tc.knn/pallas_call" stack_frame_id=3}
  %fusion.105 = s32[8] fusion(%a), kind=kLoop, metadata={op_name="jit(_itis_step)/jit(_threshold_clustering)/tc.mis/while/body/scatter-max"}
  ROOT %segment_sum.16 = f32[4,6] custom-call(%b), metadata={op_name="jit(_itis_step)/itis.reduce/pallas_call"}
}
"""


def test_scope_paths_from_the_hlo_text():
    # level 0 and level 1 in the order they ran; level 1 names its MIS
    # fusion differently
    names = scopes.by_module([HLO, HLO.replace("fusion.105", "fusion.7")])
    assert [sorted(m) for m in names["jit__itis_step"]] == [
        ["_fused_topk.6", "fusion.105", "segment_sum.16"],
        ["_fused_topk.6", "fusion.7", "segment_sum.16"]]
    host = [Event("bench.fit", 0, 100 * MS),
            Event("repro.itis.level", 0, 1 * MS),
            Event("repro.itis.level", 50 * MS, 51 * MS)]
    modules = [Event("jit__itis_step", 10 * MS, 40 * MS, "0"),
               Event("jit__itis_step", 60 * MS, 90 * MS, "0")]
    ops = [_op("_fused_topk.6", 10, 20, "jit__itis_step", ""),
           _op("fusion.105", 20, 25, "jit__itis_step", ""),
           _op("segment_sum.16", 25, 40, "jit__itis_step", ""),
           _op("fusion.7", 60, 62, "jit__itis_step", ""),
           _op("fusion.105", 62, 63, "jit__itis_step", ""),
           _op("_fused_topk.6", 63, 90, "jit__itis_step", "")]
    tr = scoped(ops, modules, host)
    # fusion.105 exists only in level 0's module: at level 1 it stays bare
    assert tr.attach_paths(names) == 5
    assert tr.scope_seconds("tc.knn") == pytest.approx(0.037)
    assert tr.scope_seconds("tc.mis") == pytest.approx(0.007)
    assert tr.scope_seconds("itis.reduce") == pytest.approx(0.015)


# ---------------------------------------------------------------------------
# the per-layer readers of scopes and counters

SCOPE_METRICS = {  # metric: device seconds per fit in synthetic()'s 2 fits
    "knn_device_s.fit": 0.026 / 2, "mis_device_s.fit": 0.005 / 2,
    "grow_device_s.fit": 0.003 / 2, "reduce_device_s.fit": 0.020 / 2,
    "kmeans_seed_device_s.fit": 0.002 / 2}
COUNTER_METRICS = {"mis_rounds.fit": 9.0, "reduce_tiles.fit": 13.0,
                   "lloyd_iters.fit": 3.0}
COUNTERS = {"mis_rounds": (np.int32(5), np.int32(4)),
            "reduce_tiles": (np.int32(10), np.int32(3)),
            "lloyd_iters": np.int32(3)}


@pytest.fixture(scope="module")
def catalog():
    return Catalog()


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_scope_reader_reads_device_seconds_per_fit(catalog, metric):
    ctx = ReadCtx(loop="fit", trace=synthetic(), fits=2)
    assert catalog.reader(metric)(ctx) == pytest.approx(SCOPE_METRICS[metric])


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_scope_reader_reads_nothing_without_its_scope(catalog, metric):
    read = catalog.reader(metric)
    tr = synthetic()
    # the trace as the harness loads it carries no scope paths
    assert read(ReadCtx(loop="fit", trace=_as_the_harness_loads_it(tr),
                        fits=2)) is None
    # a scoped trace whose operations carry no such scope
    scope = {"knn_device_s.fit": "tc.knn", "mis_device_s.fit": "tc.mis",
             "grow_device_s.fit": "tc.grow",
             "reduce_device_s.fit": "itis.reduce",
             "kmeans_seed_device_s.fit": "kmeans.seed"}[metric]
    tr.paths = {e: p for e, p in tr.paths.items()
                if not scopes.in_scope(p, scope)}
    assert read(ReadCtx(loop="fit", trace=tr, fits=2)) is None
    assert read(ReadCtx(loop="fit", trace=None, fits=2)) is None
    assert read(ReadCtx(loop="fit", trace=synthetic(), fits=0)) is None


@pytest.mark.parametrize("metric", sorted(COUNTER_METRICS))
def test_counter_reader_sums_the_last_fits_levels(catalog, metric):
    ctx = ReadCtx(loop="fit", counters=COUNTERS)
    assert catalog.reader(metric)(ctx) == COUNTER_METRICS[metric]


@pytest.mark.parametrize("metric", sorted(COUNTER_METRICS))
def test_counter_reader_reads_nothing_without_a_count(catalog, metric):
    read = catalog.reader(metric)
    name = metric[:-len(".fit")]
    assert read(ReadCtx(loop="fit")) is None
    missing = {k: v for k, v in COUNTERS.items() if k != name}
    assert read(ReadCtx(loop="fit", counters=missing)) is None
    # off the Pallas path reduce_tiles is 0 at every level: nothing read
    zero = dict(COUNTERS, **{name: (np.int32(0), np.int32(0))})
    assert read(ReadCtx(loop="fit", counters=zero)) is None


def test_the_four_itis_scopes_cover_what_they_hold(catalog):
    ctx = ReadCtx(loop="fit", trace=synthetic(), fits=2)
    four = sum(catalog.reader(m)(ctx) for m in (
        "knn_device_s.fit", "mis_device_s.fit", "grow_device_s.fit",
        "reduce_device_s.fit"))
    itis = catalog.reader("itis_device_s.fit")(ctx)
    # the unscoped copy and "tc.knn.extra", 16 ms over two fits, stay out
    assert itis - four == pytest.approx(0.008)


def test_earlier_readers_read_the_scoped_trace_as_the_plain_one(catalog):
    """Loading the trace with the program's spans and scope paths leaves
    every earlier per-layer metric as it was, to the bit."""
    from chipbench import work

    scoped_tr = synthetic()
    plain = _as_the_harness_loads_it(scoped_tr)
    assert any(e.name.startswith("repro.") for e in scoped_tr.host)
    for name in ("device_idle_frac.fit", "itis_device_s.fit",
                 "kmeans_device_s.fit", "knn_roofline.fit",
                 "itis_level0_device_s.fit"):
        read = catalog.reader(name)
        got = [read(ReadCtx(loop="fit", trace=tr, peaks=work.peaks(
            "TPU v5 lite"), d=128, knn_k=1, level_valid=[600, 200], fits=2))
            for tr in (plain, scoped_tr)]
        assert got[0] is not None and got[0] == got[1], name


def test_scope_and_counter_metrics_are_registered_on_the_fit_cells(catalog):
    """Every one on both fit cells but ``lloyd_iters.fit``, on sift1m alone:
    covertype's k = 7 lands in one optimum or another by seed, and its
    Lloyd count with it."""
    want = set(SCOPE_METRICS) | set(COUNTER_METRICS)
    for name in ("sift1m.fit", "covertype.fit"):
        cell = catalog.cell(name)
        here = want - ({"lloyd_iters.fit"} if name == "covertype.fit"
                       else set())
        assert here <= set(cell.readers)
        assert ("lloyd_iters.fit" in cell.readers) == (name == "sift1m.fit")
        for m in cell.per_layer:
            if m["name"] in want:
                assert m["moves"] == "fit_s"


def test_a_lloyd_roofline_reads_from_the_context_alone():
    """What a later reader of the Lloyd step's roofline needs is in the
    context: the backend's k, its valid prototypes, the width, the traced
    fit's Lloyd count, and the step's device time outside the seeding."""
    from chipbench import work

    def lloyd_roofline(ctx):
        tr = ctx.trace
        ns = sum(e.end - e.start for e in tr.ops
                 if e.leaf and e.group == "jit__kmeans"
                 and e.name.startswith("pairwise_sq_l2")
                 and not scopes.in_scope(tr.paths.get(e, ""), "kmeans.seed"))
        iters = scopes.counter_total(ctx, "lloyd_iters")
        w = work.lloyd_work(ctx.proto_valid, ctx.k, ctx.d, int(iters))
        return work.roofline_percent(w, ns / 1e9 / ctx.fits, ctx.peaks)

    ctx = ReadCtx(loop="fit", trace=synthetic(), fits=2,
                  peaks=work.peaks("TPU v5 lite"), d=128, k=256,
                  proto_valid=873, counters={"lloyd_iters": np.int32(2)})
    # three passes of 873 x 256 x 128, memory bound, in 8 ms over two fits
    want = 3 * 4.0 * (873 * 128 + 256 * 128 + 873) / 819e9 / 0.004 * 100
    assert lloyd_roofline(ctx) == pytest.approx(want)


def _one_fit(modules):
    host = [Event("bench.fit", 0, 100 * MS),
            Event("repro.itis.level", 0, 1 * MS),
            Event("repro.itis.level", 50 * MS, 51 * MS)]
    ops = [_op("_fused_topk.6", 10, 20, "jit__itis_step", ""),
           _op("segment_sum.16", 25, 40, "jit__itis_step", ""),
           _op("_fused_topk.6", 63, 90, "jit__itis_step", "")]
    return scoped(ops, [Event(n, s * MS, t * MS, "0") for n, s, t in modules],
                  host)


TWO_LEVELS = [("jit__itis_step", 10, 40), ("jit__itis_step", 60, 90)]


@pytest.mark.parametrize("case", ["matched", "a_call_not_recorded",
                                  "a_program_not_recorded", "no_path",
                                  "no_device_program"])
def test_attach_recorded_matches_the_calls_to_the_traced_runs(case):
    """The paths are attached only where each recorded program ran in the
    traced fit as often as it was called; a program the recording missed,
    or texts that give no operation a path, fail the run."""
    texts = [HLO, HLO]
    modules = TWO_LEVELS
    if case == "a_call_not_recorded":
        texts = [HLO]
    elif case == "a_program_not_recorded":
        modules = TWO_LEVELS + [("jit__kmeans", 92, 95)]
    elif case == "no_path":
        texts = [HLO.replace("_fused_topk.6", "other.1")
                 .replace("segment_sum.16", "other.2")] * 2
    elif case == "no_device_program":
        modules = []
    tr = _one_fit(modules)
    if case == "matched":
        assert scopes.program_runs(tr) == {"jit__itis_step": 2}
        assert scopes.attach_recorded(tr, texts) == 3
    elif case == "no_device_program":
        assert scopes.attach_recorded(tr, texts) == 0
        assert not tr.paths
    else:
        with pytest.raises(RuntimeError):
            scopes.attach_recorded(tr, texts)
