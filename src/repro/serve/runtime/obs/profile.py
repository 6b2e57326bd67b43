"""Opt-in host spans on the profiler's clock, around each stage of a flush.

Off by default: the hot path sees one module-global check per stage and
enters a shared ``nullcontext``. ``enable()`` turns every stage into a
``jax.profiler.TraceAnnotation``, a host TraceMe event that lands in the
``.xplane.pb`` on the same clock as the device's ``XLA Ops``, so a gap
on the device can be read against what each host thread was doing.

The spans, in nesting order on the dispatching thread:

  runtime.flush                       one flush (metadata: the Tracer's
                                      flush trace id, rows, bucket,
                                      replica; ``degraded`` on the exact
                                      path)
    runtime.flush.assemble            concatenate the drained requests
                                      (a flush of two or more)
    svm_engine.pad/b{bkt}             staging copy into the bucket buffer;
                                      padding rows zeroed (absent where a
                                      one-request flush's chunk fills its
                                      bucket and goes as admitted)
    svm_engine.put/b{bkt}             host -> device issue
    svm_engine.step/{family}/b{bkt}   the jitted call (the enqueue);
                                      ``svm_engine.step_exact/b{bkt}``
                                      on the degraded path
    runtime.flush.resolve             breaker, futures, telemetry, spans

and on whichever thread materializes a result first:

  svm_engine.sync                     wait until the outputs are on the host
  svm_engine.fallback                 exact re-score of rows outside Eq 3.11
    svm_engine.fallback.gather/b{bkt} the enqueue of the rows' gather, on
                                      the device, into an array of their
                                      bucket's size (absent where a call
                                      takes a chunk's device rows whole)
    svm_engine.fallback.step/b{bkt}   the exact program's enqueue
    svm_engine.fallback.sync          device wait and copy back

The scheduler enters its spans through ``annotate``; the engine, which
never imports this package, gets the factory pushed into its own seam.
Enable after warm-up: the spans are host events only and change no
compiled program.

``capture(path)`` bundles the whole flow: enable the spans, open a
``jax.profiler.trace`` session writing to ``path``, and restore the
previous state on exit. ``Runtime.profile(model, Z, path)`` uses it to
capture exactly one coalesced step.
"""

from __future__ import annotations

import contextlib
import threading

_lock = threading.Lock()
_enabled = False
_NO_SPAN = contextlib.nullcontext()


def enabled() -> bool:
    """True when profiling annotations are active."""
    return _enabled


def enable(on: bool = True) -> bool:
    """Toggle the stage spans (here and in the engine); returns the
    previous state."""
    global _enabled
    with _lock:
        prev = _enabled
        _enabled = bool(on)
        from repro.serve import svm_engine as _engine

        if _enabled:
            from jax.profiler import TraceAnnotation

            _engine.set_profile_annotation(TraceAnnotation)
        else:
            _engine.set_profile_annotation(None)
    return prev


def annotate(name: str, **metadata):
    """Context manager: ``jax.profiler.TraceAnnotation(name, **metadata)``
    when enabled, a shared no-op otherwise. Safe on every hot-path
    stage."""
    if not _enabled:
        return _NO_SPAN
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **metadata)


@contextlib.contextmanager
def capture(path):
    """Profile everything inside the block into ``path``.

    Enables annotations, records a ``jax.profiler`` trace (viewable
    with TensorBoard's profile plugin or ``perfetto``), then restores
    the previous annotation state.
    """
    import jax

    prev = enable(True)
    try:
        with jax.profiler.trace(str(path)):
            yield
    finally:
        enable(prev)
