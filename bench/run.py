"""The benchmark's command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Without ``--rehearse`` a platform other than ``tpu`` exits non-zero and
prints no result.  See ``bench/harness.py``.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout's root (for ``bench``) and ``src`` (for the program) replace
# this script's own directory, whose modules would shadow the standard ones
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    # libtpu logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.harness import main

    sys.exit(main(sys.argv[1:], t0=T0))
