"""From a profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
planes are ``/device:TPU:<i>``; on each, the ``XLA Modules`` line holds
one event per executed program (``jit__step(<hash>)`` for the engine's
step) and the ``XLA Ops`` line one event per operation, named by its HLO
text. Host planes hold the program's and the harness's
``TraceAnnotation`` spans and the runtime's own TraceMe events, on the
same clock.

  busy      union of the ``XLA Ops`` intervals of one device, clipped to
            the window (the harness's ``chipbench.window`` span)
  ops       every device operation in the window with the program it ran
            in, for readers that pick a kernel out by what the trace shows
  breakdown the device operations that took most time, and the idle gaps
            labelled by the host span that overlaps each most
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LABELLED_GAPS = 200
TOP = 10


@dataclasses.dataclass
class Op:
    device: int
    module: str          # program name without its hash, e.g. "jit__step"
    text: str            # the HLO text the trace names the op by
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: dict          # device index -> busy seconds in the window
    ops: list
    idle_gaps: list       # [[label, seconds]], most first
    devices: list

    def mean_busy_s(self) -> float:
        return float(np.mean([self.busy_s[d] for d in self.devices])) if self.devices else 0.0

    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s() / self.window_s

    def select(self, module_prefix: str, contains: str) -> tuple[int, float]:
        """(calls, device seconds) of the ops inside programs whose name
        starts with ``module_prefix`` and whose HLO text has ``contains``."""
        hits = [o for o in self.ops if o.module.startswith(module_prefix) and contains in o.text]
        return len(hits), sum(o.dur_ns for o in hits) * 1e-9

    def device_ops(self, top: int = TOP) -> list:
        total = collections.Counter()
        for o in self.ops:
            total[op_label(o)] += o.dur_ns * 1e-9
        return [[k, v] for k, v in total.most_common(top)]


_OPCODE = re.compile(r"\}?\s([a-z][a-z0-9-]*)\(")


def op_label(op: Op) -> str:
    """``<program>/<instruction> <opcode>``, e.g. ``jit__step/_step.1 custom-call``."""
    name, _, rest = op.text.partition(" = ")
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else "op"
    if "tpu_custom_call" in rest:
        opcode = "tpu_custom_call"
    return f"{op.module}/{name.lstrip('%')} {opcode}"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _module_of(modules: list, starts: np.ndarray, t: float) -> str:
    i = int(np.searchsorted(starts, t, side="right")) - 1
    if i >= 0 and t <= modules[i][1]:
        return modules[i][2]
    return "?"


def summarize(path: str, devices: list) -> Summary:
    """Reduce the ``.xplane.pb`` at ``path`` for the devices the cell uses
    (indices)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    window = None
    host = []                                   # (start, end, name)
    per_device_ops = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if dev not in devices:
                continue
            modules, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(e.start_ns, e.start_ns + e.duration_ns, e.name.split("(")[0])
                               for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
            modules.sort()
            per_device_ops[dev] = (modules, ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif not e.name.startswith("$"):
                        host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if window is None:
        starts = [o[0] for _, ops in per_device_ops.values() for o in ops]
        ends = [o[0] + o[1] for _, ops in per_device_ops.values() for o in ops]
        window = (min(starts), max(ends)) if starts else (0.0, 1.0)
    w0, w1 = window
    all_ops, busy, gaps = [], {}, []
    for dev in devices:
        modules, ops = per_device_ops.get(dev, ([], []))
        mstarts = np.array([m[0] for m in modules])
        spans = []
        for start, dur, text in ops:
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            all_ops.append(Op(dev, _module_of(modules, mstarts, start), text, s, e - s))
            spans.append((s, e))
        merged = _union(spans)
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for se in merged for x in se] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy, ops=all_ops,
                   idle_gaps=_label_gaps(gaps, host), devices=list(devices))


def _label_gaps(gaps: list, host: list) -> list:
    """Label the longest idle gaps by the host span overlapping each most
    (the shorter span on a tie) and sum their seconds per label."""
    gaps = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:LABELLED_GAPS]
    if not gaps:
        return []
    hs = np.array([h[0] for h in host], float)
    he = np.array([h[1] for h in host], float)
    total = collections.Counter()
    for a, b in gaps:
        label = "no traced host span"
        if len(hs):
            overlap = np.minimum(he, b) - np.maximum(hs, a)
            best = float(overlap.max())
            if best > 0:
                cand = np.flatnonzero(overlap >= best * (1 - 1e-9))
                pick = cand[np.argmin((he - hs)[cand])]
                label = re.sub(r"\(\d+\)$", "", host[pick][2])
        total[label] += (b - a) * 1e-9
    return [[k, v] for k, v in total.most_common(TOP)]
