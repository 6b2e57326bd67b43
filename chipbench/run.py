"""The benchmark's command: one run of one cell, on the chip it starts on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``harness.py``. Run it from the root of a checkout; it exits non-zero
and prints no result where JAX finds no TPU or fewer chips than the cell
asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    code = 1
    try:
        from chipbench import harness

        code = harness.main(sys.argv[1:], T_START)
    except Exception:                       # noqa: BLE001 — any failure exits non-zero
        traceback.print_exc()
    finally:
        sys.stdout.flush()
        sys.stderr.flush()
        # every thread and process of the run is closed above; _exit skips
        # the runtime's teardown messages that would follow the result
        os._exit(code)
