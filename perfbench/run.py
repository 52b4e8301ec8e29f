"""`python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`: one cell of BENCHMARK.json, once. The last line of
standard output is the result; see PERF.md."""
import os
import sys
import time

_T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    import logging

    logging.basicConfig(level=logging.ERROR, stream=sys.stderr)
    from perfbench.harness import main

    sys.exit(main(sys.argv[1:], _T0))
