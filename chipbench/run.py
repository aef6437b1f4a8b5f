"""Run one cell of the on-chip benchmark and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for. It exits 2 without a result anywhere else. The last line of
standard output is the result (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, then
``check``); the last lines of standard error repeat each number checked
beside its limit.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import pathlib  # noqa: E402
import sys  # noqa: E402

# import the package from the checkout's root, not this directory's modules
sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))
