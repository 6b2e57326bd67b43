"""``quadform``'s share of its roofline in the bulk cells (layer: kernels).

The kernel is the Pallas custom call inside the engine's step program
(``jit__step`` in the trace; the calls carry no name of their own). The
least time is the larger of the algorithm's operations over the bf16
peak (float32 operands at the default precision make one bf16 MXU pass)
and its bytes over HBM bandwidth, counted from the configuration's shapes
and the rows served in the traced window; the share is that least time
over the kernel's device time.
"""

from chipbench import work

FAMILIES = ("maclaurin", "poly2")


def read(run):
    if run.trace is None or run.config["family"] not in FAMILIES:
        return None
    calls, seconds = run.trace.select("jit__step", "tpu_custom_call")
    rows = run.counters.get("served_rows", 0)
    if calls == 0 or seconds <= 0 or rows <= 0:
        return None
    heads, d = int(run.config["heads"]), int(run.config["d"])
    flops = work.quadform_flops(rows, heads, d)
    nbytes = work.quadform_bytes(rows, calls, heads, d)
    least, bound = work.roofline_seconds(flops, nbytes, run.peaks["bf16_flops"],
                                         run.peaks["hbm_bytes_per_s"])
    run.note(f"quadform: {calls} calls, {rows} rows, {seconds!r} s on the device, "
             f"{flops!r} flop, {nbytes!r} B, {bound}-bound, least {least!r} s")
    return 100.0 * least / seconds
