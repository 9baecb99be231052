"""Entry point: ``python3 kgbench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout (see
``kgbench/harness.py``)."""
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout root (for ``kgbench``) and the system under test, in place
# of this script's own directory
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

from kgbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T0))
