"""The closed-loop generator against a stand-in runtime: the clients'
lead-in before the window, a window that opens with no request in flight,
and a count of only the answers that come inside it."""

import threading
import time

import numpy as np

from chipbench import drive

ROWS = 16
MIX = {"entry": "submit", "arrivals": "closed", "rows": {"dist": "fixed", "value": ROWS},
       "in_flight_per_chip": 3, "pool_rows": 64, "drain_s": 5}


class _Answer:
    def __init__(self, n):
        self.values = np.zeros((n, 2), np.float32)
        self.labels = np.zeros(n, np.int64)
        self.valid = np.ones(n, bool)


class _Future:
    def __init__(self, runtime, n):
        self.runtime, self.n = runtime, n

    def result(self, timeout=None):
        time.sleep(0.002)
        with self.runtime.lock:
            self.runtime.answered += 1
        return _Answer(self.n)


class _Runtime:
    def __init__(self):
        self.lock = threading.Lock()
        self.sent = self.answered = 0

    def submit(self, alias, Z):
        with self.lock:
            self.sent += 1
        return _Future(self, len(Z))


class _Window:
    def __init__(self, runtime):
        self.runtime = runtime

    def open(self):
        with self.runtime.lock:
            self.at_open = (self.runtime.sent, self.runtime.answered)
        self.t_open = time.perf_counter()

    def close(self):
        with self.runtime.lock:
            self.at_close = (self.runtime.sent, self.runtime.answered)


def test_the_window_opens_on_whole_requests_after_the_lead(monkeypatch):
    monkeypatch.setattr(drive, "LEAD_S", 0.2)
    runtime = _Runtime()
    window = _Window(runtime)
    pool = np.zeros((MIX["pool_rows"], 4), np.float32)
    driven = drive.bulk(runtime, "m", pool, MIX, 1, 2**33 + 7, 0.3, window)
    warm = MIX["in_flight_per_chip"]
    sent, answered = window.at_open
    assert sent == answered                      # nothing in flight as it opens
    assert sent > warm + MIX["in_flight_per_chip"]   # the clients' lead came first
    assert window.at_close[0] == window.at_close[1]
    # only answers inside the window count, as whole requests
    in_window = window.at_close[1] - answered
    assert 0 < driven.rows_in_window <= ROWS * in_window
    assert driven.rows_in_window % ROWS == 0
    assert driven.failed == driven.unanswered == 0
    per_second = next(n for n in driven.notes if "each second" in n)
    assert str(driven.rows_in_window) in per_second
