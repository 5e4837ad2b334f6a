"""Run one cell of the chip benchmark.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
      --trace <0|1>

Prints one JSON result as the last line of standard output, and the
numbers the check compared, each with its limit, as the last lines of
standard error.  Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for, where a ``REPRO_*`` variable
is set, or where the program's config differs from the cell's
configuration file.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from chipbench.harness import main
    raise SystemExit(main(t_start=T_START))
