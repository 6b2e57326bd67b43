"""``rbf_pred``'s share of its roofline in the bulk cells (layer:
kernels).

The kernel is the Pallas custom call of the exact path, found by its
name: the trace names the call's instruction after the kernel
(``rbf_pred.<n>``). The least time is the larger of the exact expansion's
operations (``work.exact_flops`` for the rows the exact path re-scored in
the traced window, ``fallback_rows``) over the bf16 peak, and its bytes
(``work.exact_bytes``, the model read once per call in the trace) over
HBM bandwidth; the share is that least time over the kernel's device
time. The bf16 peak, as ``quadform_roofline.bulk`` uses, counts the same
work whatever implements it, so the share cannot pass 100%. Both of the
kernel's GEMMs run at HIGHEST, about six bf16 MXU passes, so about 16.7%
is its ceiling; 29.3 TFLOP/s, as read on a TPU v5e at epsilon's shapes,
would be about 15%. None where no exact call ran in the window.
"""

from chipbench import work

KERNEL = "rbf_pred"


def _is_kernel(op) -> bool:
    name = op.text.partition(" = ")[0].lstrip("%")
    return name.startswith(KERNEL) and "tpu_custom_call" in op.text


def read(run):
    if run.trace is None:
        return None
    hits = [o for o in run.trace.ops if _is_kernel(o)]
    seconds = sum(o.dur_ns for o in hits) * 1e-9
    rows = run.counters.get("fallback_rows", 0)
    if not hits or seconds <= 0 or rows <= 0:
        return None
    heads, n_sv, d = (int(run.config[k]) for k in ("heads", "n_sv", "d"))
    flops = work.exact_flops(rows, heads, n_sv, d)
    nbytes = work.exact_bytes(rows, len(hits), heads, n_sv, d)
    least, bound = work.roofline_seconds(flops, nbytes, run.peaks["bf16_flops"],
                                         run.peaks["hbm_bytes_per_s"])
    run.note(f"rbf_pred: {len(hits)} calls, {rows} rows, {seconds!r} s on the device, "
             f"{flops!r} flop, {nbytes!r} B, {bound}-bound, least {least!r} s")
    return 100.0 * least / seconds
