"""The control of a cell's output check, on the chip.

  python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 0`` does and, besides the
served tokens' widest gap under the plain reference, reads the gap of
the reference computed one precision step below the served instance
(int4 weights where the instance holds int8) at every position of the
same rows.  The control has to come out as not correct: its gap above
the cell's limit (``bench/limits/<cell>.json``), while the program's
own gap stays below it.  Prints both beside the limit; the benchmark's
own runs never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    from iolmbench import main as M
    from iolmbench import spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    try:
        out = M.run_cell(cell, args.seed, args.seconds, False, control=True,
                         t_start=T0)
    except M.NoChip as e:
        M.log(f"FAIL: {e}")
        return 3
    M.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
