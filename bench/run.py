"""Run one cell of the chip benchmark (see BENCHMARK.json):

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is the result, one JSON object. Exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:]))
