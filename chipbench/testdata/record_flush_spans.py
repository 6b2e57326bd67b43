"""Record the small chip trace that ``tests/test_chipbench_flush.py`` reads.

    python3 chipbench/testdata/record_flush_spans.py [--out PATH]

One process on one TPU: the mnist-bulk deployment as the harness sets it
up (model and rows from the seed, the pinned family published and warmed,
one warm round of requests), then the harness's own traced window over
``FLUSHES`` requests of 8,192 rows, two in flight, each read back by the
client thread that sent it. The test's hand-worked values hold for this
recording: three flushes from ``SEED``. The window's ``.xplane.pb`` is copied to
``--out``. Without a TPU it exits with code 3.
"""

import argparse
import os
import shutil
import sys
import tempfile
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness, spec, trace  # noqa: E402

CELL = "mnist-bulk"
IN_FLIGHT = 2
FLUSHES = 3
SEED = 2**31 + 13


def record(out: str) -> None:
    cell = spec.load_cell(CELL)
    harness.require_chips(cell.chips)
    harness.enable_cache()
    _, pool, runtime, _ = harness.prepare(cell, SEED)
    alias, rows = cell.config_name, int(cell.traffic["rows"]["value"])
    for fut in [runtime.submit(alias, pool[:rows]) for _ in range(IN_FLIGHT)]:
        fut.result().values
    jobs = iter(range(FLUSHES))
    lock = threading.Lock()

    def client() -> None:
        while True:
            with lock:
                i = next(jobs, None)
            if i is None:
                return
            o = (i * rows) % (len(pool) - rows + 1)
            runtime.submit(alias, pool[o:o + rows]).result().values

    with tempfile.TemporaryDirectory(prefix="flush-spans-") as tdir:
        window = harness.Window(runtime, alias, tdir)
        threads = [threading.Thread(target=client) for _ in range(IN_FLIGHT)]
        window.open()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        window.close()
        shutil.copy(trace.find_xplane(tdir), out)
    runtime.close()
    harness.log(f"wrote {out}: {os.path.getsize(out)} B, {window.delta['flushes']} flushes")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(spec.BENCH_DIR, "testdata",
                                                  "mnist_flush_spans.xplane.pb"))
    args = ap.parse_args()
    try:
        record(args.out)
    except harness.NoChip as e:
        harness.log(f"refused: {e}")
        sys.exit(harness.NO_CHIP)
