"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(``breakdown`` too when traced); see ``benchmarks/README.md``.
"""
import time

T_START = time.time()   # before any heavy import: set-up counts them

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks.harness import main
    sys.exit(main(t_start=T_START))
