#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run of one cell of BENCHMARK.json, on the machine it is
started on. The last line of stdout is the result object; with no TPU,
or fewer chips than the cell asks for, it prints none and exits 3.
"""
import time

T_START = time.perf_counter()  # set-up counts from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# libtpu otherwise logs to the fixed /tmp/tpu_logs, outside the checkout.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

if __name__ == "__main__":
    from benchmark import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
