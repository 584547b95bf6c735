"""Readings for a cell's limits: the program's numbers on many seeds
and the control's on the same work, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

Each seed is a whole run of the cell (its own weights, program and
window, at the cell's own load), followed by the reference and the
control, which the cell's driver defines: for a served cell the
reference with every weight matrix rounded to float8 e4m3, whose
first-placed token is read against the float32 reference.  The control
is judged against the cell's own limits, so its ``correct`` has to
come out false.  One JSON line per seed; the benchmark's own runs never
run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=t0, control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "control": out["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
