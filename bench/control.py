"""Readings of the comparison over many seeds, for setting its limits.

    python3 bench/control.py --workload cnn_mnist.s3500_k20 \
        --seeds 11,12,13 --seconds 2

For each seed it runs the cell with a short window, as `run.py` does,
and reads every compared round twice against the reference: the
program's outputs (the lower readings of each limit) and the control's,
the reference computed in bfloat16 in the program's place (the upper
readings). One process serves all seeds. Prints one JSON line per seed
and a summary line: the largest program reading and the smallest
control reading of each number. `--fault <name>` plants one of
`faults.py`'s faults under the timed path first, so that the program's
readings are the fault's (the control, which a planted fault does not
touch, is then not read).
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the TPU runtime's logs stay inside the checkout, like its caches
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_cache" / "tpu_logs"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    cell = spec.load_cell(args.workload, ROOT)
    import contextlib
    from bench import faults, harness
    harness.configure_cache(ROOT)
    prog, ctrl = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        plant = (faults.planted(args.fault) if args.fault
                 else contextlib.nullcontext())
        try:
            with plant:
                out = harness.run(cell, seed, args.seconds, False,
                                  time.time(), control=not args.fault)
        except harness.NoChip as e:
            print(f"no result: {e}", file=sys.stderr)
            return 3
        p = {k: c["value"] for k, c in out["checks"].items()}
        c = {k: v["value"]
             for k, v in out.get("control_checks", {}).items()}
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "program": p, "control": c,
                          "correct": out["correct"],
                          "metrics": out["metrics"]}), flush=True)
        for k, v in p.items():
            prog[k] = max(prog.get(k, v), v)
        for k, v in c.items():
            ctrl[k] = min(ctrl.get(k, v), v)
    print(json.dumps({"workload": cell.name, "fault": args.fault,
                      "program_max": prog,
                      "control_min": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
