"""A run whose timed path is broken underneath comes out not correct:
once for each fault a one-chip cell can have. (The exchange between
chips is a fault of four-chip cells only.)"""
import io
import time

import pytest

from bench import harness
from bench.faults import planted

FAULTS = ["state_unchanged", "half_cohort", "selection_altered"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", ["cnn_mnist.s3500_k20",
                                  "lstm_shakespeare.s1129_k20"])
def test_fault_is_not_correct(tiny_cells, name, fault):
    with planted(fault):
        out = harness.run(tiny_cells[name], 2147483749, 0.2, False,
                          time.time(), require_tpu=False,
                          log=io.StringIO())
    assert out["correct"] is False, out["checks"]
    assert out["failed"] >= 1
