"""The program's flush spans, read from the profiler trace of a traced run.

With profiling on, the serving path records one host span per stage of a
flush (``repro.serve.runtime.obs.profile`` lists them). They are TraceMe
events in the ``.xplane.pb``, on the clock of the device's ``XLA Ops``:

  runtime.flush                  one flush, on the thread that dispatches it
    runtime.flush.assemble       the concatenate of the drained requests
    svm_engine.pad/b<bucket>     zero buffer and the copy into it
    svm_engine.put/b<bucket>     the call that issues the copy to the device
    svm_engine.step/...          the enqueue of the jitted step
    runtime.flush.resolve        breaker, futures, telemetry, tracer spans
  svm_engine.sync                the client's wait from its first read until
                                 the outputs are on the host, on the client
                                 thread that reads first

Only flushes that lie wholly inside the window (the harness's
``chipbench.window`` span) count, and the per-flush means divide by their
number; a stage counts where it lies inside a counted flush on the same
thread. ``svm_engine.sync`` runs on another thread, one per flush: its
per-flush mean is over the sync spans wholly inside the window.

The input's path to the device is timed where it happens. ``put``
returns once the copy is issued; the runtime then transposes the rows
into the device's layout and copies them, on threads of its own. So
``h2d_s`` runs from the start of a counted flush's ``svm_engine.put/*``
to the start of its step on the device (the step program's ``XLA
Modules`` event). Enqueues pair with device programs in order: each step
enqueue, in start order over the whole trace, takes the first program
that starts on the cell's devices after the enqueue starts and that no
earlier enqueue took. That holds while the devices run what the flushes
enqueue in that order and with no backlog, as in mnist-bulk (one chip,
idle 98% of the window); a backlog would add its wait to the reading.

A trace with no ``runtime.flush`` span (a program that records none)
reads as nothing: every reader returns None.

The reduced trace (``trace.Summary``) keeps no host events, so
``of(run)`` reads them from the file the harness hands every reader,
``run.trace_path``, once per run, and raises if a traced run has none.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses

from chipbench import trace

FLUSH = "runtime.flush"
STAGES = {
    "assemble": ("runtime.flush.assemble",),
    "pad": ("svm_engine.pad/",),
    "put": ("svm_engine.put/",),
    "step": ("svm_engine.step/", "svm_engine.step_exact/"),
    "resolve": ("runtime.flush.resolve",),
}
SYNC = "svm_engine.sync"


@dataclasses.dataclass
class Flushes:
    count: int               # runtime.flush spans wholly inside the window
    mean_s: float            # their mean duration
    stage_s: dict            # stage -> seconds per counted flush
    sync_s: float | None     # mean svm_engine.sync span in the window
    syncs: int
    h2d_s: float | None      # mean put start -> its step's start on the device
    h2d_count: int           # counted flushes whose step started in the trace
    idle_under_flush: float | None  # share of device idle time inside a flush

    def ms(self, stage: str) -> float:
        return 1e3 * self.stage_s[stage]


def _measure(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _events(path: str, devices: list):
    """(window, {thread line: [(start, end, name)] sorted by start}, the
    sorted start times of the programs run on ``devices``)."""
    import jax

    window, lines, programs = None, collections.defaultdict(list), []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in devices:
            programs += [e.start_ns for line in plane.lines if line.name == "XLA Modules"
                         for e in line.events]
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == trace.WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                else:
                    lines[(plane.name, i)].append(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name))
    for events in lines.values():
        events.sort()
    return window, lines, sorted(programs)


def _device_starts(lines: dict, programs: list) -> dict:
    """{start of a step enqueue: start of its program on the device},
    paired in order as the module docstring says."""
    enqueues = sorted(s for events in lines.values() for s, _, name in events
                      if name.startswith(STAGES["step"]))
    out, j = {}, 0
    for s in enqueues:
        j = bisect.bisect_left(programs, s, lo=j)
        if j == len(programs):
            break
        out[s] = programs[j]
        j += 1
    return out


def _stage_of(name: str) -> str | None:
    for stage, prefixes in STAGES.items():
        if name.startswith(prefixes):
            return stage
    return None


def load(path: str, summary) -> Flushes | None:
    """The flush spans of the ``.xplane.pb`` at ``path``; ``summary`` is
    the harness's reduction of the same trace, whose device operations
    give each chip's busy intervals."""
    window, lines, programs = _events(path, summary.devices)
    if window is None:
        return None
    w0, w1 = window
    started = _device_starts(lines, programs)
    flushes, stage_s, syncs, h2d = [], dict.fromkeys(STAGES, 0.0), [], []
    for events in lines.values():
        starts = [e[0] for e in events]
        for s, e, name in events:
            if not (w0 <= s and e <= w1):
                continue
            if name == SYNC:
                syncs.append(e - s)
            elif name == FLUSH:
                flushes.append((s, e))
                lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
                put = None
                for cs, ce, cname in events[lo:hi]:
                    stage = _stage_of(cname)
                    if stage is None or ce > e:
                        continue
                    stage_s[stage] += (ce - cs) * 1e-9
                    if stage == "put":
                        put = cs
                    elif stage == "step" and put is not None and cs in started:
                        h2d.append(started[cs] - put)
    if not flushes:
        return None
    n = len(flushes)
    in_flush = trace._union([list(f) for f in flushes])
    shares = []
    for dev in summary.devices:
        busy = trace._union([[o.start_ns, o.start_ns + o.dur_ns]
                             for o in summary.ops if o.device == dev])
        idle = (w1 - w0) - _measure(busy)
        if idle > 0:
            shares.append((_measure(in_flush) - _measure(_intersect(in_flush, busy))) / idle)
    return Flushes(
        count=n,
        mean_s=_measure(flushes) * 1e-9 / n,
        stage_s={k: v / n for k, v in stage_s.items()},
        sync_s=sum(syncs) * 1e-9 / len(syncs) if syncs else None,
        syncs=len(syncs),
        h2d_s=sum(h2d) * 1e-9 / len(h2d) if h2d else None,
        h2d_count=len(h2d),
        idle_under_flush=sum(shares) / len(shares) if shares else None,
    )


def of(run) -> Flushes | None:
    """The flush spans of this run's trace, read once and kept on ``run``;
    None without a trace or without flush spans. A traced run whose trace
    cannot be found raises: the metrics must not vanish unseen."""
    cache = vars(run)
    if "flush_spans" not in cache:
        found = None
        if run.trace is not None:
            if run.trace_path is None:
                raise RuntimeError("a traced run without its trace path: "
                                   "the flush readers cannot find the .xplane.pb")
            found = load(run.trace_path, run.trace)
        cache["flush_spans"] = found
    return cache["flush_spans"]
