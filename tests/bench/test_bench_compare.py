"""The pieces of the `model` comparison: the per-leaf gap of the norms
of change, and the program's decisions read back from its history and
carries."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import compare


def test_norm_gap_takes_the_worst_leaf_against_the_median():
    p0 = {"a": np.zeros(4), "b": np.zeros(2), "c": np.zeros(1)}
    ref = {"a": np.full(4, 1.0), "b": np.full(2, 1.0), "c": np.full(1, 1e-6)}
    # norms of change: a 2, b 1.414, c 1e-6; median 1.414
    out = {"a": np.full(4, 1.1), "b": np.full(2, 1.0),
           "c": np.full(1, 2e-6)}
    # a: 0.2 / 2 = 0.1; c: 1e-6 / 1.414, far below its own 100%
    assert compare.norm_gap(p0, out, ref) == pytest.approx(0.1)
    assert compare.norm_gap(p0, ref, ref) == 0.0
    # a model left unchanged reads 1 on the largest leaves
    assert compare.norm_gap(p0, p0, ref) == pytest.approx(1.0)


def test_chunk_decisions_put_a_failure_on_the_last_selection():
    sel = np.zeros((4, 5), bool)
    sel[[0, 2], 1] = True      # device 1: selected twice, fails the second
    sel[[0, 1, 3], 3] = True   # device 3: selected three times, never fails
    hist = {"selected": np.concatenate([np.zeros((2, 5), bool), sel]),
            "H": np.arange(30).reshape(6, 5)}
    snap = SimpleNamespace(round=2, state={
        "n_participations": np.array([0, 4, 0, 1, 0])})
    nxt = SimpleNamespace(round=6, state={
        "n_participations": np.array([0, 5, 0, 4, 0])})
    s, part, H = compare.chunk_decisions(hist, snap, nxt)
    assert (s == sel).all()
    want = sel.copy()
    want[2, 1] = False
    assert (part == want).all()
    assert (H == hist["H"][2:6]).all()
