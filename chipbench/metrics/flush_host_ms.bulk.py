"""Mean host time of one flush in the bulk cells (layer: runtime
scheduler): the ``runtime.flush`` spans wholly inside the traced window,
from the dispatching thread's entry to the end of its bookkeeping
(``chipbench/flush.py``). Notes each stage's share of it."""

from chipbench import flush


def read(run):
    f = flush.of(run)
    if f is None:
        return None
    stages = ", ".join(f"{k} {f.ms(k)!r}" for k in flush.STAGES)
    covered = sum(f.stage_s.values()) / f.mean_s
    run.note(f"flush: {f.count} in the window, {1e3 * f.mean_s!r} ms each; per flush (ms) "
             f"{stages}; the stages cover {100 * covered!r}% of it; "
             f"8192 rows per flush would be {8192 / f.mean_s!r} rows/s; "
             f"{f.syncs} syncs of {f.sync_s!r} s; put start to device start "
             f"{f.h2d_s!r} s over {f.h2d_count} flushes")
    return 1e3 * f.mean_s
