"""Share of the device's idle time in the traced window during which some
thread was inside ``runtime.flush``, averaged over the cell's chips
(layer: runtime scheduler). High: the flush thread paces the chip. Low:
something else does (client threads, the interpreter lock)
(``chipbench/flush.py``)."""

from chipbench import flush


def read(run):
    f = flush.of(run)
    return None if f is None or f.idle_under_flush is None else 100.0 * f.idle_under_flush
