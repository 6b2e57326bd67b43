"""The input's path to the device per flush in the bulk cells (layer:
host to device): from the start of ``svm_engine.put/*`` to the start of
that flush's step on the device, over the flushes wholly in the traced
window whose step started in the trace. It holds the copy's issue, the
runtime's layout transpose and copy, and the step's launch; ``put``
itself returns at issue (``chipbench/flush.py``)."""

from chipbench import flush


def read(run):
    f = flush.of(run)
    return None if f is None or f.h2d_s is None else 1e3 * f.h2d_s
