"""Record the small CPU trace that ``tests/test_chipbench_fallback.py`` reads.

    JAX_PLATFORMS=cpu python3 chipbench/testdata/record_fallback_spans.py [--out PATH]

One process on the CPU: an epsilon-shaped deployment at a test size
(``standardized`` rows, d 64, 1,024 support vectors, one head, gamma /
gamma_max 1.4, so every row lies outside Eq 3.11), published and warmed as
the harness does it, then the harness's own traced window over
``REQUESTS`` requests of 64 rows, sent and read one after another by one
client thread, so that each result's exact fallback runs on that thread.
The window's ``.xplane.pb`` is copied to ``--out``. The trace has no
device plane: it pins the span readers, never a device reading.
"""

import argparse
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import deploy, harness, spec, trace  # noqa: E402

CONFIG = {"d": 64, "heads": 1, "n_sv": 1024, "features": "standardized", "gamma_ratio": 1.4,
          "family": "maclaurin", "dtype": "float32"}
ROWS = 64
REQUESTS = 3
SEED = 2**31 + 16
ALIAS = "epsilon-tiny"


def record(out: str) -> None:
    from repro.serve import Runtime

    model = deploy.make_model(CONFIG, SEED)
    pool = deploy.make_pool(CONFIG, SEED, REQUESTS * ROWS)
    runtime = Runtime(engine_opts={"min_bucket": 32, "max_batch": ROWS})
    engines = deploy.publish(runtime, CONFIG, model, {"warm_buckets": [ROWS]}, ALIAS)
    runtime.submit(ALIAS, pool[:ROWS]).result().values       # the exact program compiles
    with tempfile.TemporaryDirectory(prefix="fallback-spans-") as tdir:
        window = harness.Window(runtime, ALIAS, engines, tdir)
        window.open()
        for i in range(REQUESTS):
            runtime.submit(ALIAS, pool[i * ROWS:(i + 1) * ROWS]).result().values
        window.close()
        shutil.copy(trace.find_xplane(tdir), out)
    runtime.close()
    harness.log(f"wrote {out}: {os.path.getsize(out)} B, {window.delta['flushes']} flushes, "
                f"{window.delta['fallback_rows']} fallback rows")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(spec.BENCH_DIR, "testdata",
                                                  "epsilon_fallback_spans.xplane.pb"))
    record(ap.parse_args().out)
