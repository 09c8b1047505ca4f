"""Entry point of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints the result as the last line of standard output.  Exits non-zero,
with no result, where JAX finds no TPU or fewer chips than the cell asks.
"""
import sys
from pathlib import Path

if __name__ == "__main__":
    root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(root), str(root / "src")]
    from benchmarks.chip.harness import main

    raise SystemExit(main())
