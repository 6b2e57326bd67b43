"""The traffic generator and the entry a window drives.

A traffic mix is data (``traffic/<mix>.json``). Its keys:

  entry               "submit": ``Runtime.submit`` in-process, the library
                      path a batch job uses.
  arrivals            "closed": ``in_flight_per_chip`` x chips requests in
                      flight, the next sent when one completes; the
                      clients send for ``LEAD_S`` before the window
                      opens, and a request counts where its answer comes
                      inside the window.
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
LEAD_S = 1.0                 # bulk: seconds of the same traffic before the window opens


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
    state = {"attempted": 0, "failed": 0, "unanswered": 0, "done": 0}
    kept: list = []
    errors: list = []
    answered: list = []                 # host clock at each whole answer
    stop = [np.inf]                     # no request is sent from then on
    send = threading.Event()            # cleared: each client ends its request, then waits
    send.set()
    idle = threading.Semaphore(0)       # one release per client that waits
    from jax.profiler import TraceAnnotation

    def client() -> None:
        while time.perf_counter() < stop[0]:
            if not send.is_set():
                idle.release()
                send.wait()
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
                answered.append(done)
                state["done"] += 1
                i = state["done"]
                if len(kept) < keep_n:
                    kept.append(answer)
                else:
                    j = int(keep_rng.integers(0, i))
                    if j < keep_n:
                        kept[j] = answer

    # set-up: one round of requests down the timed path, so the window
    # does not see a process's first flushes. Each answer is read as a
    # client reads it: the result is materialized on first read, and rows
    # outside Eq 3.11 are then re-scored by the exact path, whose program
    # compiles on its first call. A request answered with an error here
    # counts among the failed and the unanswered.
    warm = [runtime.submit(alias, cyclic[o:o + rows])
            for o in (np.arange(in_flight) * rows) % len(pool)]
    warm_errors = 0
    for fut in warm:
        try:
            fut.result(timeout=drain_s).values
        except Exception as e:                     # noqa: BLE001 — counted, reported
            warm_errors += 1
            errors.append(f"warm-up: {e!r}")
    # the clients' own traffic runs LEAD_S before the window, so that their
    # threads and buffers are in use when it opens; it opens once every
    # request of that lead has been answered, so the window's counters
    # hold whole requests. An answer counts where it comes inside the window.
    threads = [threading.Thread(target=client, name=f"chipbench-client-{i}")
               for i in range(in_flight)]
    for t in threads:
        t.start()
    time.sleep(LEAD_S)
    send.clear()
    for _ in threads:
        idle.acquire(timeout=drain_s)
    window.open()
    stop[0] = window.t_open + seconds
    send.set()
    for t in threads:
        t.join()
    window.close()
    at = np.asarray(answered) - window.t_open
    at = at[(at >= 0) & (at <= seconds)]
    rows_in_window = rows * len(at)
    per_second = rows * np.bincount(np.minimum(at, seconds - 1e-9).astype(int),
                                    minlength=int(np.ceil(seconds)))
    notes = [f"bulk: {state['attempted']} requests of {rows} rows, {in_flight} in flight, "
             f"{LEAD_S} s of them before the window, {rows_in_window} rows completed "
             f"in the {seconds} s window",
             f"bulk: rows completed in each second of the window {per_second.tolist()}"]
    notes += [f"bulk error: {e}" for e in errors[:5]]
    # a request that raised has no answer, as one that never came; so has
    # a warm-up request that raised
    no_answer = state["failed"] + state["unanswered"] + warm_errors
    return Driven(attempted=state["attempted"], failed=no_answer,
                  unanswered=no_answer, window_s=seconds,
                  rows_in_window=rows_in_window, answers=kept, notes=notes)
