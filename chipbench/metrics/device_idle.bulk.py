"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (layer: device). Read from the profiler
trace: 1 - union of the ``XLA Ops`` intervals / window."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share()
