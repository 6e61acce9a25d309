"""The benchmark's work counts against hand counts, and its peaks."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import peaks, spec

ROOT = Path(__file__).resolve().parents[2]


def _ref(name):
    cell = spec.load_cell(name, ROOT)
    return cell.config, spec.reference_model(cell)


def test_cnn_mnist_counts():
    cfg, ref = _ref("cnn_mnist.s3500_k20")
    # conv1 28·28·9·32·2 + conv2 14·14·288·64·2 + fc1 3136·512·2 + fc2
    # 512·10·2
    assert ref.forward_flops(cfg) == (451_584 + 7_225_344 + 3_211_264
                                      + 10_240)
    assert ref.forward_flops(cfg) == 10_898_432
    # conv1 9·32+32, conv2 9·32·64+64, fc1 3136·512+512, fc2 512·10+10
    assert ref.n_params(cfg) == 320 + 18_496 + 1_606_144 + 5_130
    assert ref.n_params(cfg) == 1_630_090 == cfg["n_params"]
    # forward + every weight gradient + every input gradient but conv1's
    assert ref.train_flops(cfg) == 3 * 10_898_432 - 451_584


def test_lstm_shakespeare_counts():
    cfg, ref = _ref("lstm_shakespeare.s1129_k20")
    per_step = 2 * (8 * 1024 + 256 * 1024 + 256 * 80)
    assert ref.forward_flops(cfg) == 79 * per_step == 45_948_928
    assert ref.train_flops(cfg) == 3 * 45_948_928
    # embedding 80·8, input 8·1024, recurrent 256·1024, bias 1024, head
    # 256·80 + 80
    assert ref.n_params(cfg) == 292_560 == cfg["n_params"]


@pytest.mark.parametrize("name", ["cnn_mnist.s3500_k20",
                                  "lstm_shakespeare.s1129_k20"])
def test_reference_params_match_the_count(name):
    import jax
    cfg, ref = _ref(name)
    params = jax.eval_shape(lambda k: ref.init(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) \
        == ref.n_params(cfg)


def _reader(name):
    return spec.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                            name.replace(".", "_"))


def test_kernel_work_from_shapes():
    sel = _reader("kernel.rewafl_select_roofline")
    # seven (S,) float32 leaves in, K indices and K flags out
    assert sel.work(5000, 20) == (12 * 5000, 7 * 4 * 5000 + 2 * 4 * 20)
    avg = _reader("kernel.fedavg_roofline")
    # a (K, P) float32 stack and K weights in, P values out; a multiply
    # and an add per stacked value
    assert avg.work(20, 1000) == (2 * 20 * 1000,
                                  4 * 20 * 1000 + 4 * 20 + 4 * 1000)


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    # bytes bound: 819 bytes take a nanosecond
    assert peaks.roofline_seconds(1.0, 819.0, "TPU v5 lite") \
        == pytest.approx(1e-9)


def test_mfu_counts_live_iterations_only():
    mfu = _reader("mfu")
    tr = SimpleNamespace(window_s=1.0)
    hist = {"selected": np.zeros((2, 10), bool),
            "mean_H_selected": np.array([5.0, 7.0])}
    hist["selected"][:, :3] = True
    ctx = SimpleNamespace(
        trace=tr, rounds=2, evals=1, S=10, K=3, probe=4, batch=2,
        n_test=6, forward_flops=100.0, train_flops=300.0,
        history=hist, peaks={"flops_per_s": 1e6}, n_chips=1)
    want = (2 * 10 * 4 * 100 + (5 * 3 + 7 * 3) * 2 * 300
            + 6 * 4 * 100 + 6 * 100)
    assert mfu.read(ctx) == pytest.approx(100 * want / 1e6)
    assert mfu.read(SimpleNamespace(trace=None)) is None


def test_every_per_layer_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        mod = _reader(m["name"])
        assert callable(mod.read)
