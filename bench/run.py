"""Run one benchmark cell once:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout on a machine with the chips the cell asks
for.  It exits non-zero, and prints no result, when JAX finds no TPU or
fewer chips than the cell needs.  See `bench/harness.py`.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime logs under TPU_LOG_DIR, or a fixed /tmp path without it
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.run(t_start=T_START))
