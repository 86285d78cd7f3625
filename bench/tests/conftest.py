"""The benchmark's self-tests: `python -m pytest bench/tests` from the
checkout's root, on the CPU (`JAX_PLATFORMS=cpu`)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
