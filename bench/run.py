"""Run one benchmark cell once; the last line of standard output is its
result.

    python3 bench/run.py --workload yi6b.chat_poisson --seed 7 \
        --seconds 30 --trace 0

Run it from the root of a checkout, on a machine that holds the chips
the cell asks for. It exits non-zero, printing no result, when JAX finds
no TPU or fewer chips than the cell needs. ``--trace 1`` prints the
cell's per-layer metrics instead of its end-to-end ones.
"""

import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
