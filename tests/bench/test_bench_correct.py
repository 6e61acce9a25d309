"""The comparison that decides `correct`, driven through a whole run at
CPU size (the look for a chip skipped): the program passes, and the
control, the reference computed in bfloat16 in its place, fails."""
import io
import time

import numpy as np
import pytest

from bench import harness

CELLS = ["cnn_mnist.s3500_k20", "lstm_shakespeare.s1129_k20"]


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_control_fails(tiny_cells, name):
    out = harness.run(tiny_cells[name], 2147483713, 0.2, False,
                      time.time(), require_tpu=False, log=io.StringIO(),
                      control=True)
    assert out["correct"] is True
    assert out["attempted"] == 2 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    ctrl = out["control_checks"]
    over = [k for k, c in ctrl.items()
            if not (np.isfinite(c["value"]) and c["value"] <= c["limit"])]
    assert over, f"the control passed every limit: {ctrl}"
    m = out["metrics"]
    assert m["device_rounds_per_s"]["value"] > 0
    assert m["setup_s"]["value"] > 0
