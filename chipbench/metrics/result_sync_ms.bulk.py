"""The client's result wait per flush in the bulk cells (layer: result
wait): the mean ``svm_engine.sync`` span wholly inside the traced
window, one per flush, on the client thread that reads the result
first, from its first read until the outputs are on the host. It holds
whatever of the copy to the device is still to run, the step and the
copy back (``chipbench/flush.py``)."""

from chipbench import flush


def read(run):
    f = flush.of(run)
    return None if f is None or f.sync_s is None else 1e3 * f.sync_s
