"""The program's own spans in a profiler trace: device operations grouped by
their ``jax.named_scope`` path, the program's ``repro.*`` host spans, and
device time per ITIS level; and the fit's own counters.

``load`` reads a trace with :func:`chipbench.trace.load` and adds the
program's host spans (``repro.*``), so the idle gaps fall under the
innermost of them by :meth:`Trace.host_label`'s rule.

The scope path of an operation is the ``op_name`` XLA keeps in its
metadata (``jit(_itis_step)/jit(_threshold_clustering)/tc.knn/...``). A
TPU v5e trace, as ``ProfileData`` of jaxlib 0.9.0 reads it, does not carry
it: an operation has no stat but its device offset and duration, its name
is the instruction's text without metadata, and a program is named by a
fingerprint (``jit__itis_step(1538...)``), not by its module id. So the
path comes from the optimized HLO text of each program that ran
(:func:`op_names`), instruction by instruction, a fit's n-th run of a
program taken as the n-th module of that name given
(:meth:`ScopedTrace.attach_paths`). Paths are kept beside the trace's
events, in ``ScopedTrace.paths``. :func:`recording` records the calls of
the level and k-means programs while a fit runs,
:func:`compiled_texts` gives each call's optimized HLO text afterwards,
and :func:`attach_recorded` attaches their paths once it has matched the
calls recorded to the programs' runs in the trace.

The names of the scopes, spans and counters read here are the program's
contract (DESIGN.md §20). The readers of ``metrics/`` take them per fit
with :func:`scope_seconds_per_fit` and :func:`counter_total`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import os
import re
import shutil
from typing import Dict, List, Optional

import numpy as np

from chipbench import trace as trace_mod
from chipbench.trace import Event, Trace

HOST_PREFIX = "repro."
LEVEL_SPAN = "repro.itis.level"
LEVEL_PROGRAM = r"_itis_step"
FIT_SPAN = "bench.fit"
#: device scopes of the fit path, in the order a level runs them
ITIS_SCOPES = ("tc.knn", "tc.mis", "tc.grow", "itis.reduce")
BACKEND_SCOPES = ("kmeans.seed",)
BACKEND_PROGRAM = r"_kmeans"
#: the programs whose calls :func:`recording` records, by module and name
PROGRAMS = (("repro.core.itis", "_itis_step"),
            ("repro.cluster.kmeans", "_kmeans"))
#: the name a trace gives their runs (``jit__itis_step``)
RECORDED_MODULES = tuple(f"jit_{attr}" for _, attr in PROGRAMS)

_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HLO_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*'
                        r'op_name="([^"]*)"')


def op_names(hlo: str) -> Dict[str, str]:
    """``{instruction: op_name}`` of one optimized HLO module's text."""
    out = {}
    for line in hlo.splitlines():
        m = _HLO_INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def module_of(hlo: str) -> str:
    """The module's name (``jit__itis_step``), as a trace names its runs."""
    m = _HLO_MODULE.match(hlo)
    return m.group(1) if m else ""


def by_module(texts: List[str]) -> Dict[str, List[Dict[str, str]]]:
    """``{program: [op_names(module), ...]}`` of optimized HLO module
    texts, each program's modules in the order given."""
    out: Dict[str, List[Dict[str, str]]] = {}
    for t in texts:
        out.setdefault(module_of(t), []).append(op_names(t))
    return out


def in_scope(path: str, scope: str) -> bool:
    """``scope`` is one whole component of ``path``."""
    return scope in path.split("/")


def fits(tr: Trace) -> List[Event]:
    """The benchmark's span around each fit (``bench.fit``), in order."""
    return sorted((e for e in tr.host if e.name == FIT_SPAN),
                  key=lambda e: e.start)


def level_seconds(tr: Trace, levels: Optional[int] = None,
                  program: str = LEVEL_PROGRAM,
                  span: str = LEVEL_SPAN) -> Optional[List[float]]:
    """Device seconds of each ITIS level, averaged over the fits: a fit's
    n-th execution of ``program`` is its level n. Each fit must run as
    many as ``levels``, or, where that is not given, as its ``span``s
    (one per level run); ``None`` where a count differs, or no fit ran."""
    prog = re.compile(program)
    execs = sorted((e for e in tr.modules if prog.search(e.name)),
                   key=lambda e: e.start)
    per_fit = []
    for f in fits(tr):
        want = levels if levels is not None else sum(
            e.name == span and f.start <= e.start < f.end for e in tr.host)
        runs = [e for e in execs if f.start <= e.start < f.end]
        if not want or len(runs) != want:
            return None
        per_fit.append([_device_ns(tr, prog, r) / 1e9 for r in runs])
    if not per_fit:
        return None
    return [sum(col) / len(per_fit) for col in zip(*per_fit)]


def _device_ns(tr: Trace, prog, run: Event) -> int:
    """Leaf device time of ``prog``'s operations inside one execution."""
    return sum(e.end - e.start for e in tr.ops
               if e.leaf and prog.search(e.group)
               and run.start <= e.start < run.end)


@dataclasses.dataclass
class ScopedTrace(Trace):
    #: each device operation's scope path, where one is known
    paths: Dict[Event, str] = dataclasses.field(default_factory=dict)

    def scope_seconds(self, scope: str, module: Optional[str] = None) -> float:
        """Device seconds of the leaf operations whose scope path has
        ``scope`` as one component, in programs matching ``module``."""
        mod_re = re.compile(module) if module else None
        ns = 0
        for e in self.ops:
            if not e.leaf or not in_scope(self.paths.get(e, ""), scope):
                continue
            if mod_re is not None and not mod_re.search(e.group):
                continue
            ns += e.end - e.start
        return ns / 1e9

    def attach_paths(self, names: Dict[str, List[Dict[str, str]]]) -> int:
        """Give each operation without a scope path the ``op_name`` of its
        instruction in ``names`` (:func:`by_module`):
        a fit's n-th run of a program is the n-th module given for it.
        Returns how many operations took a path."""
        by_prog: Dict[str, list] = {}
        for f in fits(self):
            seen: Dict[str, int] = {}
            for m in sorted(self.modules, key=lambda e: e.start):
                if f.start <= m.start < f.end and m.name in names:
                    n = seen.get(m.name, 0)
                    seen[m.name] = n + 1
                    by_prog.setdefault(m.name, []).append((m, n))
        done = 0
        for e in self.ops:
            if e in self.paths or e.group not in by_prog:
                continue
            for m, n in by_prog[e.group]:
                if m.start <= e.start < m.end and n < len(names[e.group]):
                    path = names[e.group][n].get(e.name, "")
                    if path:
                        self.paths[e] = path
                        done += 1
                    break
        return done

    def idle_by_span(self) -> Dict[str, float]:
        """Idle device seconds in the window by the shortest host span
        running at each instant (``untraced host work`` where none is).
        Finer than :meth:`idle_gaps`, whose one name per gap is that of a
        span covering most of it."""
        spans = [e for e in self.host if e.name != trace_mod.WINDOW]
        out: Dict[str, float] = {}
        for s, t in self.gaps():
            cuts = sorted({s, t} | {c for e in spans for c in (e.start, e.end)
                                    if s < c < t})
            for a, b in zip(cuts, cuts[1:]):
                inside = [e for e in spans if e.start <= a and b <= e.end]
                name = (min(inside, key=lambda e: e.end - e.start).name
                        if inside else "untraced host work")
                out[name] = out.get(name, 0.0) + (b - a) / 1e9
        return out


def program_spans(path: str) -> List[Event]:
    """The program's host spans (``repro.*``) in the trace under ``path``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    host = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return host


def load(path: str, *, remove: bool = False) -> ScopedTrace:
    """The trace under ``path`` (the directory given to start_trace), as
    :func:`chipbench.trace.load` reads it, with the program's host spans
    added; ``remove`` deletes the directory once it is read."""
    tr = trace_mod.load(path)
    host = sorted(tr.host + program_spans(path), key=lambda e: e.start)
    if remove:
        shutil.rmtree(path, ignore_errors=True)
    return ScopedTrace(tr.ops, tr.modules, host, tr.window, tr.chips)


@contextlib.contextmanager
def recording(calls: list):
    """Record each call of the level and k-means programs in ``calls``
    while the block runs: the jitted function and its arguments, each
    array by its shape and dtype only, so that no buffer outlives the
    call (an uncommitted array lowers to the same program)."""
    import jax

    def shape_of(a):
        return (jax.ShapeDtypeStruct(a.shape, a.dtype)
                if isinstance(a, jax.Array) else a)

    saved = []
    for mod_name, attr in PROGRAMS:
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, attr)
        saved.append((mod, attr, fn))

        def call(*args, _fn=fn, **kwargs):
            calls.append((_fn, jax.tree.map(shape_of, args),
                          jax.tree.map(shape_of, kwargs)))
            return _fn(*args, **kwargs)

        setattr(mod, attr, call)
    try:
        yield
    finally:
        for mod, attr, fn in saved:
            setattr(mod, attr, fn)


def compiled_texts(calls: list) -> List[str]:
    """The optimized HLO text of each recorded call's executable, as the
    process compiled it or loaded it from the compile cache."""
    return [fn.lower(*a, **kw).compile().as_text() for fn, a, kw in calls]


def program_runs(tr: Trace) -> Dict[str, int]:
    """Runs of each recorded program (:data:`RECORDED_MODULES`) inside the
    fits' spans of ``tr``."""
    out: Dict[str, int] = {}
    for f in fits(tr):
        for m in tr.modules:
            if m.name in RECORDED_MODULES and f.start <= m.start < f.end:
                out[m.name] = out.get(m.name, 0) + 1
    return out


def attach_recorded(tr: ScopedTrace, texts: List[str]) -> int:
    """Give ``tr`` the scope paths of the recorded calls' compiled
    ``texts`` (:func:`compiled_texts`); returns how many operations took a
    path. Each recorded program must have run in the traced fits as many
    times as :func:`recording` saw it called, and some operation must take
    a path: otherwise the program no longer calls them where
    :func:`recording` looks, every scope metric would read nothing, and
    this raises. A trace with no device program (a CPU run) takes none."""
    if not tr.modules:
        return 0
    names = by_module(texts)
    recorded = {m: len(v) for m, v in names.items()}
    ran = program_runs(tr)
    if recorded != ran:
        raise RuntimeError(f"recorded program calls {recorded} are not the "
                           f"runs traced in the fit {ran}")
    done = tr.attach_paths(names)
    if done <= 0:
        raise RuntimeError(f"no traced operation took a scope path from the "
                           f"recorded programs {recorded}")
    return done


# ---------------------------------------------------------------------------
# what the per-layer readers take


def scope_seconds_per_fit(ctx, scope: str, program: str) -> Optional[float]:
    """Device seconds per fit of the leaf operations inside ``scope`` in
    the programs matching ``program``; None where the trace carries no
    scope paths or the scope took no time."""
    tr = ctx.trace
    if ctx.loop != "fit" or not ctx.fits or not isinstance(tr, ScopedTrace):
        return None
    s = tr.scope_seconds(scope, module=program)
    return s / ctx.fits if s > 0 else None


def counter_total(ctx, name: str) -> Optional[float]:
    """The last fit's counter ``name`` (``FitResult.counters``), summed
    over the levels where it holds one a level; None where the fit has no
    such counter or it counted nothing."""
    if ctx.loop != "fit" or name not in ctx.counters:
        return None
    total = float(np.sum(ctx.counters[name]))
    return total if total > 0 else None
