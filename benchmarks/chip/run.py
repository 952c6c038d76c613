"""Run one benchmark cell on the chip and print its result line.

  python3 -m benchmarks.chip.run --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

Run from the root of a checkout. JAX's persistent compilation cache is
kept in ``<checkout>/.jax_cache``, so only a cell's first run in a
checkout compiles. A run that finds no TPU, or fewer chips than the cell
asks for, exits non-zero and prints no result.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import harness

    harness.prepare()
    return harness.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
