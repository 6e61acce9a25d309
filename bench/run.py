"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload cnn_mnist.s3500_k20 --seed 7 \
        --seconds 10 --trace 0

`--trace 0` reports the cell's end-to-end metrics (device-rounds per
second over the window, and set-up seconds); `--trace 1` runs a short
profiled window instead and reports the per-layer metrics read from the
trace. Either way the run ends with the comparison against the plain
reference, printed as `check=` lines on standard error and under
`checks` in the result. Exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the TPU runtime's logs stay inside the checkout, like its caches
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_cache" / "tpu_logs"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    cell = spec.load_cell(args.workload, ROOT)

    from bench import harness
    harness.configure_cache(ROOT)
    prof = ROOT / ".bench_cache" / "profile"
    shutil.rmtree(prof, ignore_errors=True)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T0, prof_dir=str(prof))
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(prof, ignore_errors=True)
    print(harness.result_line(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
