"""Run one benchmark cell once and print its result as the last line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout: the program under test is ``src/``, the
cells are ``BENCHMARK.json``'s.  Exits 2, printing no result, where JAX
finds no TPU, fewer chips than the cell asks for, a device kind missing
from ``bench/peaks.json``, or no program.
"""

import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
