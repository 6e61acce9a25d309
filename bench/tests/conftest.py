"""Fixtures of the benchmark's own CPU tests, run with
`PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests`: the
repo root and `src` on the path, and the benchmark's cells cut to a size
the CPU runs in seconds."""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def shrink(cell):
    """The cell at CPU size: narrow model, 20 devices, short loops. The
    round, the comparison and the limits stay the cell's own."""
    cell = copy.deepcopy(cell)
    c = cell.config
    if c["model"] == "cnn":
        kw = {"c1": 4, "c2": 8, "d_fc": 16}
    else:
        kw = {"d_embed": 8, "d_hidden": 16}
        c["seq_len"] = c["data"]["seq_len"] = 12
    c.update(kw)
    c["program"]["kwargs"] = kw
    c["data"]["n_test"] = 16
    t = cell.traffic
    t.update(clients=20, select=4, per_client=8, chunk=2,
             compare_chunks=[0, 1])
    t["fl"].update(H_max=3, probe_size=4, batch_size=4)
    return cell


@pytest.fixture(scope="module")
def tiny_cells():
    from bench import spec
    return {name: shrink(spec.load_cell(name, ROOT))
            for name in ("cnn_mnist.s3500_k20", "lstm_shakespeare.s1129_k20")}
