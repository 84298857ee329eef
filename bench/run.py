"""Run one benchmark cell on the chip(s) this machine holds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; see ``bench/iolmbench``.
Exits non-zero, with no result line, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T0 = time.perf_counter()            # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

if __name__ == "__main__":
    from iolmbench.main import main
    sys.exit(main(t_start=T0))
