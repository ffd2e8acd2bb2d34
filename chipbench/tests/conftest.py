import os
import sys

# the program's kernels run in interpret mode off the chip, as in tests/
os.environ.setdefault("REPRO_KERNEL_MODE", "interpret")
ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path[:0] = [os.path.abspath(ROOT), os.path.abspath(os.path.join(ROOT,
                                                                    "src"))]
