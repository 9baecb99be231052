"""``python3 -m kgbench`` = ``python3 kgbench/run.py``."""
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from kgbench.harness import main  # noqa: E402

sys.exit(main(t_start=T0))
