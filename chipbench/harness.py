"""One run of one cell: set up, measure, check, print one line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): the cell's files, the chip check, the
model and the request rows drawn from the seed on the device, the pinned
family compiled and published with its exact model, the cell's buckets
warmed (every compile is served from the persistent cache after a
checkout's first run). Then the window, then the comparison with the
plain reference once the program's state is freed.

Standard output ends with one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, ``breakdown`` when traced,
and last ``checks``: each number compared with its limit. Standard error
ends with the same numbers, one per line.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 3 and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import tempfile
import time

import numpy as np

from chipbench import check, drive, reference, spec

NO_CHIP = 3


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_peaks(kind: str, root: str = spec.ROOT) -> dict:
    """The peaks of one device kind; a kind the table lacks is an error."""
    with open(os.path.join(root, "chipbench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
    return table[kind]


def require_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked for, {len(devs)} present")
    return devs[:chips]


class Window:
    """Opens and closes the measured window: program counters before and
    after, a ``chipbench.window`` span, and with tracing the profiler with
    the program's own annotations on (only after warm-up, so no compiled
    program changes).

    The counters are the runtime's for the cell's alias, and
    ``fallback_rows``: rows the engines re-scored by the exact path."""

    def __init__(self, runtime, alias: str, engines: list, trace_dir: str | None):
        self.runtime, self.alias, self.trace_dir = runtime, alias, trace_dir
        self.engines = engines
        self.compiles = 0
        self._open = False
        self._span = None
        import jax

        def on_event(event: str, duration: float, **_kw) -> None:
            if self._open and event.startswith("/jax/core/compile/"):
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def counters(self) -> dict:
        st = self.runtime.stats(self.alias)
        out = {k: st[k] for k in ("rows", "flushes", "served_rows", "served_requests",
                                  "failed_requests", "batch_failures")}
        out["fallback_rows"] = sum(e.stats.fallback_instances for e in self.engines)
        return out

    def open(self) -> None:
        import jax

        if self.trace_dir is not None:
            from repro.serve.runtime.obs import profile

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            profile.enable(True)
        self.before = self.counters()
        self.usage = resource.getrusage(resource.RUSAGE_SELF)
        self._span = jax.profiler.TraceAnnotation("chipbench.window")
        self._span.__enter__()
        self._open = True
        self.t_open = time.perf_counter()

    def close(self) -> None:
        import jax

        self.t_close = time.perf_counter()
        self._open = False
        self._span.__exit__(None, None, None)
        after = self.counters()
        self.delta = {k: after[k] - self.before[k] for k in after}
        u0, u1 = self.usage, resource.getrusage(resource.RUSAGE_SELF)
        self.host_note = (
            f"host in the window: user {u1.ru_utime - u0.ru_utime!r} s, system "
            f"{u1.ru_stime - u0.ru_stime!r} s, involuntary switches "
            f"{u1.ru_nivcsw - u0.ru_nivcsw}, load average {os.getloadavg()[0]!r}, "
            f"{os.cpu_count()} cores")
        if self.trace_dir is not None:
            from repro.serve.runtime.obs import profile

            jax.profiler.stop_trace()
            profile.enable(False)


class Run:
    """What a per-layer metric's ``read(run)`` sees: the cell's
    configuration and chips, the reduced trace (``trace.Summary``) and the
    path of the ``.xplane.pb`` it was reduced from, the window's counters
    (``Window``) and the device's peaks."""

    def __init__(self, cell, trace, counters, peaks, trace_path: str | None = None):
        self.config = cell.config
        self.chips = cell.chips
        self.trace = trace
        self.trace_path = trace_path
        self.counters = counters
        self.peaks = peaks
        self.notes: list = []

    def note(self, msg: str) -> None:
        self.notes.append(msg)


def end_to_end(cell, driven: drive.Driven, setup_s: float) -> dict:
    out = {}
    for m in cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            value = setup_s
        elif name == "rows_per_s":
            value = driven.rows_in_window / driven.window_s
        else:
            raise spec.SpecError(f"cell {cell.name!r} cannot report {name!r}")
        out[name] = {"value": value, "unit": m["unit"]}
    return out


def per_layer(cell, run: Run, root: str = spec.ROOT) -> dict:
    out = {}
    for m in cell.per_layer:
        value = spec.load_metric(m["name"], root).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def gather(answers: list, width: int):
    """Concatenate the answers: pool rows, scores, labels, valid."""
    if not answers:
        return (np.zeros(0, np.int64), np.zeros((0, width), np.float32),
                np.zeros(0, np.int64), np.zeros(0, bool))
    return (np.concatenate([a.picks for a in answers]),
            np.concatenate([a.scores for a in answers]),
            np.concatenate([a.labels for a in answers]),
            np.concatenate([a.valid for a in answers]))


def judge(cell, model, pool, driven: drive.Driven, control: bool = False):
    """The numbers compared, from every kept answer against the reference
    computed once per pool row; with ``control`` also the control's."""
    picks, scores, labels, valid = gather(driven.answers, model.heads)
    ref = reference.exact_scores(model.X, model.alpha, model.b, model.gamma, pool)
    ref_valid = reference.envelope_valid(model.X, model.gamma, pool)
    multiclass = model.heads > 1
    numbers = check.compare(scores, labels, valid, ref[picks], ref_valid[picks],
                            multiclass=multiclass)
    numbers["unanswered"] = driven.unanswered
    ctl = None
    if control:
        low = reference.control_scores(model.X, model.alpha, model.b, model.gamma, pool)[picks]
        low_labels = np.argmax(low, axis=1) if multiclass else np.where(low[:, 0] >= 0, 1, -1)
        ctl = check.compare(low, low_labels, ref_valid[picks], ref[picks], ref_valid[picks],
                            multiclass=multiclass)
        ctl["unanswered"] = 0
    return numbers, ctl, len(picks)


def parse(argv):
    ap = argparse.ArgumentParser(description="One run of one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control's numbers (not in a benchmark run)")
    return ap.parse_args(argv)


def enable_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding
    every program however fast it compiled."""
    import jax

    from repro import compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {compile_cache.enable()}")


def prepare(cell, seed: int):
    """The deployment: model and pool drawn from the seed, the pinned
    family published in a ``Runtime`` with the program's defaults (or the
    cell's settings) and warmed. Returns (model, pool, runtime, engines)."""
    from chipbench import deploy
    from repro.serve import Runtime

    model = deploy.make_model(cell.config, seed)
    pool = deploy.make_pool(cell.config, seed, int(cell.traffic["pool_rows"]))
    runtime = Runtime(**cell.options.get("runtime", {}))
    engines = deploy.publish(runtime, cell.config, model, cell.options, cell.config_name)
    return model, pool, runtime, engines


def execute(args, t_start: float, *, root: str = spec.ROOT, chips_check=require_chips,
            trace_dir: str | None = None, cache: bool = True) -> dict:
    """Everything after the argument parse; returns the result line's
    object and prints the notes. ``chips_check`` returns the devices the
    cell runs on (tests drive the rest of a run on the CPU through it)."""
    cell = spec.load_cell(args.workload, root)
    devices = chips_check(cell.chips)
    if cache:
        enable_cache()
    model, pool, runtime, engines = prepare(cell, args.seed)
    alias = cell.config_name
    compiled_warm = sum(e.stats.compiled_steps for e in engines)
    window = Window(runtime, alias, engines, trace_dir)
    driven = drive.bulk(runtime, alias, pool, cell.traffic, cell.chips, args.seed,
                        args.seconds, window)
    setup_s = window.t_open - t_start
    stats = runtime.stats(alias)
    recompiles = sum(e.stats.compiled_steps for e in engines) - compiled_warm
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    runtime.close()
    # the window's compile listener stays registered for the process:
    # drop its references so the program's state can be freed
    window.runtime = window.engines = None
    del engines, runtime
    gc.collect()

    notes = [f"setup_s {setup_s!r}", *driven.notes, window.host_note,
             f"window: {window.compiles} compile events, {recompiles} engine recompiles, "
             f"rows {window.delta['rows']} flushes {window.delta['flushes']} "
             f"fallback_rows {window.delta['fallback_rows']}",
             f"account: batch_failures={stats['batch_failures']} "
             f"failed_requests={stats['failed_requests']} breaker={stats['breaker']['state']} "
             f"degraded_rows={stats['breaker']['degraded_rows']}",
             f"memory_peak_bytes {peak}"]
    trace = xplane = None
    if trace_dir is not None:
        from chipbench import trace as trace_mod

        xplane = trace_mod.find_xplane(trace_dir)
        trace = trace_mod.summarize(xplane, [d.id for d in devices])
    t_ref = time.perf_counter()
    numbers, ctl, compared = judge(cell, model, pool, driven, control=bool(args.control))
    notes.append(f"reference: {compared} served rows compared in "
                 f"{time.perf_counter() - t_ref!r} s")
    limits = cell.config["limits"]
    if ctl is not None:
        notes += check.lines(ctl, limits, prefix="control")
        notes.append(f"control correct {check.verdict(ctl, limits)} (it has to be False)")
    dev0 = devices[0]
    result = {
        "correct": compared > 0 and check.verdict(numbers, limits),
        "attempted": driven.attempted,
        "failed": driven.failed,
        "metrics": {},
        "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(peak)},
    }
    if trace is None:
        result["metrics"] = end_to_end(cell, driven, setup_s)
    else:
        peaks = load_peaks(dev0.device_kind, root)
        run = Run(cell, trace, window.delta, peaks, trace_path=xplane)
        result["metrics"] = per_layer(cell, run, root)
        notes += run.notes
        result["device"]["busy_s"] = trace.mean_busy_s()
        result["device"]["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.device_ops(), "idle_gaps": trace.idle_gaps}
    result["checks"] = check.as_checks(numbers, limits)
    for line in notes:
        log(line)
    for line in check.lines(numbers, limits):
        log(line)
    return result


def main(argv, t_start: float) -> int:
    args = parse(argv)
    try:
        if args.trace:
            with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
                result = execute(args, t_start, trace_dir=tdir)
        else:
            result = execute(args, t_start)
    except NoChip as e:
        log(f"refused: {e}")
        return NO_CHIP
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0

