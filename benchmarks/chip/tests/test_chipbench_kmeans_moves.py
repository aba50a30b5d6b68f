"""The blocked ``kmeans_moves`` against the broadcast form it replaced, kept
here as the oracle: the same count on random data, on rows planted at the
slack and at ties, with invalid rows and with empty clusters; and the size
of a k = 4,096 coarse quantizer in bounded time and memory."""
import os
import sys
import time
import tracemalloc

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

from chipbench import reference  # noqa: E402
from chipbench.reference import ROUNDING, segment_mean  # noqa: E402


def broadcast_moves(protos, mass, valid, labels, k: int) -> int:
    """The count as the parent computed it: every distance in the
    difference form, the whole (p, k, d) difference at once."""
    v = np.asarray(valid, bool) & (np.asarray(labels) >= 0)
    p = np.asarray(protos, np.float64)[v]
    lab = np.asarray(labels)[v]
    if p.shape[0] == 0:
        return 1
    centres, den = segment_mean(p, lab, k, weights=np.asarray(mass)[v])
    present = den > 0
    cp = centres[present]
    d = ((p[:, None, :] - cp[None, :, :]) ** 2).sum(-1)
    near = d.argmin(axis=1)
    col = (np.cumsum(present) - 1)[lab]
    rows = np.arange(p.shape[0])
    pn, cn = (p * p).sum(-1), (cp * cp).sum(-1)
    slack = ROUNDING * (2 * pn + cn[col] + cn[near])
    return int((d[rows, col] - d[rows, near] > slack).sum())


def _random(seed=0, p=600, k=24, d=16):
    """Overlapping clusters labelled by centres drawn apart from the
    data's: many rows off the fixed point, many on it."""
    rng = np.random.default_rng(seed)
    centres = rng.normal(size=(k, d))
    protos = centres[rng.integers(0, k, p)] + rng.normal(size=(p, d))
    guess = rng.normal(size=(k, d))
    lab = ((protos[:, None] - guess[None]) ** 2).sum(-1).argmin(1)
    mass = rng.uniform(0.5, 4.0, p)
    return protos.astype(np.float32), mass, np.ones(p, bool), lab, k


def _ties(seed=0, d=4, a=3.0):
    """Two centres at ±a on the first axis, held by heavy members; light
    rows labelled with the left centre whose margin over the right one
    lies at the slack, off by a few float64 roundings either way, and rows
    equidistant from both."""
    rng = np.random.default_rng(seed)
    heavy = np.zeros((2, d))
    heavy[:, 0] = [-a, a]
    rows, labs = [heavy[0], heavy[1]], [0, 1]
    for rel in (-1e-9, -1e-12, -1e-14, 0.0, 1e-14, 1e-12, 1e-9):
        for _ in range(6):
            y = np.zeros(d)
            y[1:] = rng.normal(size=d - 1) * 0.5
            x = 0.0
            for _ in range(4):  # margin 4·a·x equal to the slack
                x = ROUNDING * (2 * (x * x + y @ y) + 2 * a * a) / (4 * a)
            y[0] = x * (1 + rel)
            rows.append(y)
            labs.append(0)
    for _ in range(6):  # ties of the nearest centre
        y = np.zeros(d)
        y[1:] = rng.normal(size=d - 1) * 0.5
        y[0] = rng.choice([-1, 1]) * 1e-15
        rows.append(y)
        labs.append(0)
    protos = np.array(rows)
    mass = np.full(len(rows), 1e-12)
    mass[:2] = 1e6
    return protos, mass, np.ones(len(rows), bool), np.array(labs), 2


def _invalid(seed=1):
    protos, mass, valid, lab, k = _random(seed)
    valid = valid.copy()
    valid[::7] = False
    lab = lab.copy()
    lab[3::11] = -1
    protos = protos.copy()
    protos[::7] = np.nan  # what an invalid row holds never counts
    return protos, mass, valid, lab, k


def _empty(seed=2):
    """Labels use only some of k centres; one used label has no mass."""
    protos, mass, valid, lab, k = _random(seed, k=10)
    lab = np.where(lab % 3 == 0, lab + 1, lab) % 10
    mass = mass.copy()
    mass[lab == 4] = 0.0
    return protos, mass, valid, lab, k + 20


CASES = {"random": _random, "ties": _ties, "invalid": _invalid,
         "empty": _empty}


@pytest.mark.parametrize("block", [reference.MOVES_BLOCK, 64, 7])
@pytest.mark.parametrize("case", sorted(CASES))
def test_blocked_kmeans_moves_equals_the_broadcast_form(case, block,
                                                        monkeypatch):
    args = CASES[case]()
    settled = []
    orig = reference._moves_by_difference

    def spy(p, *rest):
        settled.append(p.shape[0])
        return orig(p, *rest)

    monkeypatch.setattr(reference, "_moves_by_difference", spy)
    monkeypatch.setattr(reference, "MOVES_BLOCK", block)
    want = broadcast_moves(*args)
    assert reference.kmeans_moves(*args) == want
    if case == "ties":
        # the planted rows were settled in the difference form
        assert sum(settled) >= 30
        assert 0 < want < sum(settled)
    if case == "random":
        assert 50 < want < 550


def test_blocked_kmeans_moves_at_coarse_quantizer_size():
    """p = 30,852 prototypes (sift1m's level-2 valid rows), k = 4,096,
    d = 128: the broadcast form would hold 129 GB. Every row sits by its
    own centre but the planted ones, relabelled with a far centre at a
    mass too small to move it."""
    p, k, d = 30_852, 4_096, 128
    rng = np.random.default_rng(3)
    centres = rng.normal(size=(k, d)) * 10
    lab = np.arange(p) % k
    protos = (centres[lab] + rng.normal(size=(p, d)) * 0.1).astype(np.float32)
    mass = np.ones(p)
    planted = rng.choice(p, 37, replace=False)
    lab[planted] = (lab[planted] + 1 + rng.integers(0, k - 1, 37)) % k
    mass[planted] = 1e-9
    valid = np.ones(p, bool)
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        t0 = time.perf_counter()
        got = reference.kmeans_moves(protos, mass, valid, lab, k)
        seconds = time.perf_counter() - t0
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    assert got == 37
    assert seconds < 20, seconds
    one_block = reference.MOVES_BLOCK * k * 8
    inputs = p * d * 8 + p * 8 * 8  # the float64 rows and per-row vectors
    assert peak < one_block + inputs + 2 ** 24, (peak, one_block)
