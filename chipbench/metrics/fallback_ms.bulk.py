"""Mean host time of one result's exact fallback in the bulk cells
(layer: exact path): the ``svm_engine.fallback`` spans wholly inside the
traced window, on the client thread that reads the result first, from
the start of the gather to the re-scored rows on the host
(``chipbench/fallback.py``). Notes each stage's share of it."""

from chipbench import fallback


def read(run):
    f = fallback.of(run)
    if f is None:
        return None
    stages = ", ".join(f"{k} {f.ms(k)!r}" for k in fallback.STAGES)
    run.note(f"fallback: {f.count} in the window ({f.staged} with stage spans), "
             f"{1e3 * f.mean_s!r} ms each; per fallback (ms) {stages}")
    return 1e3 * f.mean_s
