"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  It needs a TPU and exits non-zero without
one.  The last line of standard output is the result as one JSON object;
the compared numbers, each beside its limit, are the last lines of
standard error.  ``bench/lib/harness.py`` says what a run does.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
