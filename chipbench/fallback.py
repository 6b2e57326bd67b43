"""The exact path's fallback spans, read from the profiler trace of a
traced run.

With profiling on, the engine re-scores the rows of a result that lie
outside Eq 3.11 inside one span, on the client thread that reads the
result first, with one span per stage inside it
(``repro.serve.runtime.obs.profile`` lists them):

  svm_engine.fallback                   one result's exact re-score
    svm_engine.fallback.gather/b<bkt>   host gather of the rows into a
                                        staging buffer of the bucket's size
    svm_engine.fallback.put/b<bkt>      the call that issues the copy to
                                        the device
    svm_engine.fallback.step/b<bkt>     the enqueue of the exact program
    svm_engine.fallback.sync            the device wait and the copy back

Only fallbacks that lie wholly inside the window (the harness's
``chipbench.window`` span) count, and the per-fallback means divide by
their number; a stage counts where it lies inside a counted fallback on
the same thread.

A trace with no ``svm_engine.fallback`` span reads as nothing, and one
whose fallbacks hold no stage spans (a program that records only the
whole fallback) gives no stage times: the readers return None for what
they cannot find. The events come from the file the harness hands every
reader, ``run.trace_path``, read once per run.
"""

from __future__ import annotations

import bisect
import dataclasses

from chipbench import flush

FALLBACK = "svm_engine.fallback"
STAGES = {
    "gather": "svm_engine.fallback.gather/",
    "put": "svm_engine.fallback.put/",
    "step": "svm_engine.fallback.step/",
    "sync": "svm_engine.fallback.sync",
}


@dataclasses.dataclass
class Fallbacks:
    count: int           # svm_engine.fallback spans wholly inside the window
    mean_s: float        # their mean duration
    stage_s: dict        # stage -> seconds per counted fallback
    staged: int          # counted fallbacks holding at least one stage span

    def ms(self, *stages: str) -> float:
        return 1e3 * sum(self.stage_s[s] for s in stages)


def _stage_of(name: str) -> str | None:
    for stage, prefix in STAGES.items():
        if name.startswith(prefix):
            return stage
    return None


def load(path: str, summary) -> Fallbacks | None:
    """The fallback spans of the ``.xplane.pb`` at ``path``; ``summary``
    is the harness's reduction of the same trace (its devices)."""
    # flush._events is shared with this module: one parse of the trace's
    # host lines for both readers' span tables
    window, lines, _ = flush._events(path, summary.devices)
    if window is None:
        return None
    w0, w1 = window
    spans, stage_s, staged = [], dict.fromkeys(STAGES, 0.0), 0
    for events in lines.values():
        starts = [e[0] for e in events]
        for s, e, name in events:
            if name != FALLBACK or not (w0 <= s and e <= w1):
                continue
            spans.append(e - s)
            lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
            inside = [(cs, ce, _stage_of(cname)) for cs, ce, cname in events[lo:hi]]
            inside = [(cs, ce, st) for cs, ce, st in inside if st is not None and ce <= e]
            staged += bool(inside)
            for cs, ce, stage in inside:
                stage_s[stage] += (ce - cs) * 1e-9
    if not spans:
        return None
    n = len(spans)
    return Fallbacks(count=n, mean_s=sum(spans) * 1e-9 / n,
                     stage_s={k: v / n for k, v in stage_s.items()}, staged=staged)


def of(run) -> Fallbacks | None:
    """The fallback spans of this run's trace, read once and kept on
    ``run``; None without a trace or without fallback spans. A traced run
    whose trace cannot be found raises: the metrics must not vanish
    unseen."""
    cache = vars(run)
    if "fallback_spans" not in cache:
        found = None
        if run.trace is not None:
            if run.trace_path is None:
                raise RuntimeError("a traced run without its trace path: "
                                   "the fallback readers cannot find the .xplane.pb")
            found = load(run.trace_path, run.trace)
        cache["fallback_spans"] = found
    return cache["fallback_spans"]
