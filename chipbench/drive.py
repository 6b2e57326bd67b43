"""The traffic generator and the entry a window drives.

A traffic mix is data (``traffic/<mix>.json``). Its keys:

  entry               "submit": ``Runtime.submit`` in-process, the library
                      path a batch job uses.
  arrivals            "closed": ``in_flight_per_chip`` x chips requests in
                      flight, the next sent when one completes.
  rows                request size: {"dist": "fixed", "value": n}.
  in_flight_per_chip  see ``arrivals``.
  pool_rows           distinct rows drawn from the seed; each request is a
                      seeded contiguous run of the pool, taken cyclically.
  drain_s             how long past the window an answer may still come.

Every seed gets the same work (the same number and size of requests in
flight); the seed changes which rows are scored.

``bulk`` returns a ``Driven``: what was attempted and failed, the window
as the host clock saw it, and the answers that are compared (a seeded
reservoir of whole requests, which holds more rows than a window can
keep).
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

KEEP_ROWS = 1 << 21          # bulk: rows of whole requests kept for the comparison


@dataclasses.dataclass
class Answer:
    picks: np.ndarray          # pool rows, in request order
    scores: np.ndarray         # (n, K)
    labels: np.ndarray
    valid: np.ndarray


@dataclasses.dataclass
class Driven:
    attempted: int
    failed: int
    unanswered: int
    window_s: float
    rows_in_window: int
    answers: list
    notes: list = dataclasses.field(default_factory=list)


def _as_2d(values) -> np.ndarray:
    v = np.asarray(values, np.float32)
    return v[:, None] if v.ndim == 1 else v


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of a run's seed (of any size)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  sum(map(ord, stream)) * 7919 + len(stream)])


def bulk(runtime, alias: str, pool: np.ndarray, mix: dict, chips: int, seed: int,
         seconds: float, window) -> Driven:
    if (mix["entry"], mix["arrivals"], mix["rows"]["dist"]) != ("submit", "closed", "fixed"):
        raise ValueError(f"no generator for entry {mix['entry']!r}, arrivals "
                         f"{mix['arrivals']!r}, sizes {mix['rows']['dist']!r}")
    rows = int(mix["rows"]["value"])
    in_flight = int(mix["in_flight_per_chip"]) * chips
    drain_s = float(mix["drain_s"])
    cyclic = np.concatenate([pool, pool[:rows]])      # any offset is a view
    offsets = rng_for(seed, "offsets")
    keep_rng = rng_for(seed, "keep")
    keep_n = max(1, KEEP_ROWS // rows)
    lock = threading.Lock()
    state = {"attempted": 0, "failed": 0, "unanswered": 0, "rows": 0, "done": 0}
    kept: list = []
    errors: list = []
    go = threading.Event()
    from jax.profiler import TraceAnnotation

    def client() -> None:
        go.wait()
        while time.perf_counter() < t_close:
            with lock:
                o = int(offsets.integers(0, len(pool)))
                state["attempted"] += 1
            try:
                with TraceAnnotation("chipbench.submit"):
                    fut = runtime.submit(alias, cyclic[o:o + rows])
                res = fut.result(timeout=drain_s)
                values, labels, valid = res.values, res.labels, res.valid
                if len(values) != rows:
                    raise ValueError(f"{len(values)} rows answered of {rows}")
            except TimeoutError:
                with lock:
                    state["unanswered"] += 1
                continue
            except Exception as e:                 # noqa: BLE001 — counted, reported
                with lock:
                    state["failed"] += 1
                    errors.append(repr(e))
                continue
            done = time.perf_counter()
            answer = Answer(np.arange(o, o + rows) % len(pool), _as_2d(values),
                            np.asarray(labels), np.asarray(valid))
            with lock:
                if done <= t_close:
                    state["rows"] += rows
                state["done"] += 1
                i = state["done"]
                if len(kept) < keep_n:
                    kept.append(answer)
                else:
                    j = int(keep_rng.integers(0, i))
                    if j < keep_n:
                        kept[j] = answer

    # set-up: one round of requests down the timed path, so the window
    # does not see a process's first flushes
    warm = [runtime.submit(alias, cyclic[o:o + rows])
            for o in (np.arange(in_flight) * rows) % len(pool)]
    for fut in warm:
        fut.result(timeout=drain_s)
    threads = [threading.Thread(target=client, name=f"chipbench-client-{i}")
               for i in range(in_flight)]
    for t in threads:
        t.start()
    window.open()
    t_close = window.t_open + seconds
    go.set()
    for t in threads:
        t.join()
    window.close()
    notes = [f"bulk: {state['attempted']} requests of {rows} rows, {in_flight} in flight, "
             f"{state['rows']} rows completed in the {seconds} s window"]
    notes += [f"bulk error: {e}" for e in errors[:5]]
    # a request that raised has no answer, as one that never came
    no_answer = state["failed"] + state["unanswered"]
    return Driven(attempted=state["attempted"], failed=no_answer,
                  unanswered=no_answer, window_s=seconds,
                  rows_in_window=state["rows"], answers=kept, notes=notes)
