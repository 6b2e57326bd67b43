"""Production SVM prediction engine — the paper's application layer (§5).

A stream of feature vectors needs decision values at minimum latency
(object detection under heavy traffic). The engine serves a compiled
approximation ARTIFACT — any ``repro.core.families`` family: the paper's
Maclaurin quadratic form, the §3.2 poly-2 expansion, or random Fourier
features — through that family's fused backend path, and enforces the
family's accuracy contract at run time. Artifacts may be f32 or int8
(``dtype="int8"`` compiles): the family's scorer dispatches on
``artifact.dtype`` to the fused dequantizing kernels, each bucket's
``TileConfig`` resolves under the int8 kernel's own tuning family
(``quadform_q8`` / ``rff_score_q8``), and the engine's contract is
otherwise unchanged — same buckets, same validity mask, same fallback.
A bare ``ApproxModel`` is still accepted (wrapped into a maclaurin
artifact), so pre-families callers keep working. Design:

Shape buckets, bounded jit cache
  Traffic arrives with arbitrary batch sizes; naive jit would recompile
  per distinct shape. Every batch is padded host-side to the next
  power-of-two bucket (floored at ``min_bucket``, capped at ``max_batch``
  — longer batches are chunked), so the engine owns at most
  log2(max_batch / min_bucket) + 1 compiled variants and steady-state
  serving performs ZERO recompilations. The padded input is not donated:
  no output has its (bucket, d) shape, so there is nothing to alias it to.

Per-bucket tile tuning
  Each bucket resolves its own ``TileConfig`` at trace time from the
  ``repro.kernels.common.tuning`` registry, keyed on the FAMILY's serving
  kernel (``quadform`` for maclaurin/poly2, ``rff_score`` for fourier)
  and shape bucket — a measured entry from the checked-in table if there
  is one, else the kernel default — so ``warmup()`` precompiles the TUNED
  variant of every bucket, not one fixed block size. Resolved configs are
  kept in ``bucket_configs`` for observability; an explicit
  ``tile_config`` argument pins all buckets (A/B runs).

One fused compiled step
  The step scores ALL K heads with a single backend call (one pallas_call
  on TPU / one or two GEMMs under XLA — not K vmapped passes), and fuses
  the family's row-validity computation and the multiclass argmax (or
  binary sign) into the same executable. K = 1 is just the smallest stack.

Head-sharded extreme multiclass (``head_mesh=``)
  In the extreme-OvR regime (K in the thousands) the stacked Hessian
  (K, d, d) is the operand that outgrows one device. A ``head_mesh``
  partitions the heads over the mesh's first axis via the family's
  ``score_sharded`` path (shard_map over the fused per-shard primitive);
  K is padded up to the axis size with argmax- and validity-neutral
  heads, the per-row argmax and validity AND reduce across shards inside
  the compiled step, and ``_finalize`` slices the score columns back to
  the real K. f32 quadform/dense-RFF artifacts only (int8 + sharding
  raises). Orthogonal to ``mesh``, which shards the EXACT path's SVs.

Deferred synchronization
  ``submit`` returns an ``EngineResult`` holding device-resident outputs;
  nothing blocks until the caller materializes ``.values`` / ``.labels`` /
  ``.valid``. A caller pipelining many batches pays one sync at the end,
  not one per batch. ``predict`` is the synchronous convenience wrapper.

Exact fallback (bounded-accuracy serving)
  Each family defines what "inside the accuracy contract" means. The
  quadform families check the Eq 3.11 bound per instance at zero extra
  cost (||z||^2 is a by-product of the envelope); the fourier family has
  no per-row envelope — its contract is the compile-time held-out error
  estimate, so validity is a per-ARTIFACT verdict broadcast over the
  batch (violating artifacts send every row down the exact path). Invalid
  rows are re-scored with the exact expansion via the streaming
  ``rbf_pred`` path (Pallas kernel on TPU: SV tiles streamed
  flash-attention style, never materializing the (n, n_sv) kernel
  matrix). The exact model reaches that program as arguments, never as
  constants: one small program per bucket serves every engine of the
  same shapes and fits the persistent compile cache. The fallback reads
  the rows the fast step ran on, on the device: a chunk whose rows all
  fall back goes to the exact program as it is, and other invalid rows
  are gathered on the device into an array of their bucket's size, so
  no row crosses to the device twice and any count of them compiles at
  most one exact program per bucket. With a
  ``mesh``, the support vectors are sharded across devices (shard_map +
  psum over the first mesh axis) so arbitrarily large exact models serve
  the slow path too. The paper recommends
  adhering to the bound; the fallback is our beyond-paper extension for
  inputs outside the verified envelope.

Statistics are kept for observability (fallback rate, padding overhead,
bucket histogram, compile count).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import backend, families
from repro.core.families import CompiledArtifact
from repro.core.maclaurin import ApproxModel
from repro.core.rbf import SVMModel
from repro.kernels.common import TileConfig, tuning

Array = jax.Array

# Profiling seam: repro.serve.runtime.obs.profile installs a context-
# manager factory (jax.profiler.TraceAnnotation) here so each stage of a
# step (pad, put, step, sync, fallback) shows up as a named host span on
# the device trace's clock. The engine never imports obs, and the
# disabled hot path costs one module-global None check per stage.
_profile_annotation = None
_NO_SPAN = contextlib.nullcontext()
_NO_NAMES = (None, None, None)


def set_profile_annotation(factory) -> None:
    """Install (or clear, with None) a ``name -> context manager`` factory
    wrapped around each stage of every engine step."""
    global _profile_annotation
    _profile_annotation = factory


def _annotate(name: str | None):
    # a span starts when its TraceAnnotation is made: make it at the with
    factory = _profile_annotation
    return _NO_SPAN if factory is None or name is None else factory(name)


def bucket_size(n: int, min_bucket: int = 32, max_batch: int = 8192) -> int:
    """Next power-of-two bucket for a batch of n rows (n <= max_batch).

    Delegates to the canonical policy in ``kernels.common.tuning`` so the
    engine's buckets, the sweep's recorded keys and the dispatch-level
    lookups can never drift apart.
    """
    return tuning.bucket(n, lo=min_bucket, hi=max_batch)


def _labels(scores, multiclass: bool):
    return (jnp.argmax(scores, axis=-1) if multiclass
            else jnp.where(scores[:, 0] >= 0, 1, -1))


@functools.lru_cache(maxsize=None)
def _exact_programs(mesh: Mesh | None):
    """The exact expansion through the streaming ``rbf_pred`` path, as two
    jitted functions of the rows and the model:

      exact_scores(Zb, X, alpha_y, gamma, b)  -> (m, K) scores
      exact_step(Zp, X, alpha_y, gamma, b)    -> (scores, valid, labels),
                                                 valid all False, shaped
                                                 like the engine's step

    ``alpha_y`` is (K, n_sv): every head shares ONE pass over the SVs.
    The support vectors, coefficients, gamma and bias are arguments, never
    constants of the executable, so the program is small, the persistent
    compile cache holds it, and every engine of the same shapes shares one
    compiled program per bucket. The static ``backend_name`` keys the
    programs on the backend their trace reads. With a ``mesh`` the SVs
    arrive sharded over its first axis and the partial sums are psum'd.
    """
    if mesh is None:
        def scores(Zb, X, alpha_y, gamma, b):
            return backend.rbf_scores(Zb, X, alpha_y, gamma, b)
    else:
        axis = mesh.axis_names[0]

        def _partial(Zb, Xs, ays, gamma):
            f = backend.rbf_scores(Zb, Xs, ays, gamma, 0.0)   # (m, K)
            return jax.lax.psum(f, axis)                      # replicated

        # check_vma=False: the Pallas rbf_pred call carries no vma type
        sharded = jax.shard_map(
            _partial,
            mesh=mesh,
            in_specs=(P(), P(axis, None), P(None, axis), P()),
            out_specs=P(),
            check_vma=False,
        )

        def scores(Zb, X, alpha_y, gamma, b):
            return sharded(Zb, X, alpha_y, gamma) + jnp.reshape(b, (1, -1))

    def exact_scores(Zb, X, alpha_y, gamma, b, *, backend_name):
        del backend_name                          # a cache key only
        return scores(Zb, X, alpha_y, gamma, b)

    def exact_step(Zp, X, alpha_y, gamma, b, *, backend_name, multiclass):
        del backend_name
        out = scores(Zp, X, alpha_y, gamma, b)
        return out, jnp.zeros((Zp.shape[0],), bool), _labels(out, multiclass)

    return (jax.jit(exact_scores, static_argnames=("backend_name",)),
            jax.jit(exact_step, static_argnames=("backend_name", "multiclass")))


@jax.jit
def _gather_rows(x, idx):
    """The rows ``x[idx]`` of a chunk on the device; an index past the
    chunk gives a zero row. One program per (chunk bucket, call bucket)."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


@dataclasses.dataclass
class EngineStats:
    """Serving counters, safe under concurrent ``submit()`` callers.

    The micro-batching runtime drives one engine from many threads, so
    every mutation goes through a lock — bare ``x += 1`` on the dataclass
    fields loses updates under contention (CPython interleaves the
    LOAD/STORE pair). Reads of individual counters stay lock-free (single
    attribute loads are atomic); ``snapshot()`` gives a consistent view.
    """

    batches: int = 0
    instances: int = 0
    fallback_instances: int = 0
    fallback_batches: int = 0           # exact-path calls of the fallback
    fallback_in_place: int = 0          # of them, on a chunk's device rows as sent
    compiled_steps: int = 0             # bucket variants traced (compile count)
    padded_instances: int = 0           # wasted rows from bucket padding
    unstaged_chunks: int = 0            # chunks sent without a staging copy
    degraded_batches: int = 0           # submit_exact batches (breaker open)
    degraded_instances: int = 0
    bucket_hits: dict = dataclasses.field(default_factory=dict)
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False
    )

    def record_batch(self, n: int, buckets: list[tuple[int, int]],
                     unstaged: int = 0) -> None:
        """One submit(): n rows chunked into [(bucket, rows_used), ...],
        ``unstaged`` of the chunks sent to the device as they came."""
        with self._lock:
            self.batches += 1
            self.instances += n
            self.unstaged_chunks += unstaged
            for bkt, m in buckets:
                self.padded_instances += bkt - m
                self.bucket_hits[bkt] = self.bucket_hits.get(bkt, 0) + 1

    def record_fallback(self, k: int, calls: int, in_place: int) -> None:
        """One result's fallback: k rows re-scored in ``calls`` exact calls,
        ``in_place`` of them on a chunk's device rows as they were sent."""
        with self._lock:
            self.fallback_instances += k
            self.fallback_batches += calls
            self.fallback_in_place += in_place

    def record_degraded(self, n: int, unstaged: int = 0) -> None:
        """One ``submit_exact`` batch of n rows (kept OUT of the fast-path
        batch/instance counters so ``fallback_rate`` — drift's signal —
        is not polluted by breaker-degraded traffic), ``unstaged`` of its
        chunks sent to the device as they came."""
        with self._lock:
            self.degraded_batches += 1
            self.degraded_instances += n
            self.unstaged_chunks += unstaged

    def record_compile(self) -> None:
        with self._lock:
            self.compiled_steps += 1

    def snapshot(self) -> dict:
        """Consistent point-in-time copy of every counter (plain dict)."""
        with self._lock:
            return {
                "batches": self.batches,
                "instances": self.instances,
                "fallback_instances": self.fallback_instances,
                "fallback_rate": self.fallback_instances / max(1, self.instances),
                "fallback_batches": self.fallback_batches,
                "fallback_in_place": self.fallback_in_place,
                "compiled_steps": self.compiled_steps,
                "padded_instances": self.padded_instances,
                "padding_overhead": self.padded_instances / max(1, self.instances),
                "unstaged_chunks": self.unstaged_chunks,
                "degraded_batches": self.degraded_batches,
                "degraded_instances": self.degraded_instances,
                "bucket_hits": dict(self.bucket_hits),
            }

    @property
    def fallback_rate(self) -> float:
        return self.fallback_instances / max(1, self.instances)

    @property
    def padding_overhead(self) -> float:
        return self.padded_instances / max(1, self.instances)


class EngineResult:
    """Device-resident scores for one submitted batch; host sync deferred.

    Each accessor materializes on first use (one device->host transfer,
    then the exact fallback for rows outside the Eq 3.11 envelope).
    """

    def __init__(self, engine: "SVMEngine", rows: list | None, chunks,
                 sent: list | None = None):
        self._engine = engine
        self._rows = rows                # each chunk's device rows, as the
                                         # fast step ran on them (fallback
                                         # re-scores); None when no fallback
                                         # can happen
        self._chunks = chunks            # [(scores, valid, labels), n_rows]
        # The host arrays sent, never read: held so that the thread that
        # drops this result frees them, for the batcher the client thread
        # that made the admitted array. Freed by the flush thread or the
        # runtime's once their transfer ends, 25.6 MB arrays came back to
        # the client threads as fresh pages: mnist-bulk on a TPU v5e fell
        # from 2.4M to 0.95M rows/s.
        self._sent = sent
        self._done = None
        self._sync = threading.Lock()    # scatter consumers race to be first
        self.on_materialize = None       # scheduler latency hook (fires once)

    def block_until_ready(self) -> "EngineResult":
        for out, _ in self._chunks:
            jax.block_until_ready(out)
        return self

    def _materialize(self):
        # The micro-batcher hands slices of one result to many client
        # threads; the first accessor runs _finalize exactly once (it
        # mutates fallback counters — double-running would double-count).
        with self._sync:
            if self._done is None:
                self._done = self._engine._finalize(self._rows, self._chunks)
                self._rows = None        # free the device rows once read
                if self.on_materialize is not None:
                    # the hook receives the finalized (values, valid,
                    # labels) so the scheduler can record per-row validity
                    # (the drift window) along with the latency sample
                    self.on_materialize(self._done)
        return self._done

    def split(self, sizes) -> list["SliceResult"]:
        """Scatter hook: carve this result into per-request row spans.

        ``sizes`` are the row counts of the requests that were coalesced
        (in submission order, summing to this result's n). Each returned
        ``SliceResult`` is a zero-copy deferred view — the parent still
        materializes ONCE on first access from any slice, so coalescing
        keeps the engine's deferred-sync property end to end.
        """
        spans, start = [], 0
        for sz in sizes:
            spans.append(SliceResult(self, start, start + sz))
            start += sz
        total = sum(m for _, m in self._chunks)
        if start != total:
            raise ValueError(f"split sizes sum to {start}, result has {total} rows")
        return spans

    @property
    def values(self) -> np.ndarray:
        """(n,) decision values (binary) or (n, K) per-class scores."""
        return self._materialize()[0]

    @property
    def valid(self) -> np.ndarray:
        """(n,) bool — row satisfied the Eq 3.11 envelope (fast path used)."""
        return self._materialize()[1]

    @property
    def labels(self) -> np.ndarray:
        """(n,) labels: {-1, +1} (binary) or argmax class index (OvR)."""
        return self._materialize()[2]


class SliceResult:
    """One request's rows out of a coalesced ``EngineResult``.

    Same accessor surface as ``EngineResult`` (``values`` / ``valid`` /
    ``labels`` / ``block_until_ready``); materializing any slice
    materializes the shared parent once and every sibling becomes free.
    """

    def __init__(self, parent: EngineResult, start: int, stop: int):
        self._parent = parent
        self._start = start
        self._stop = stop

    def __len__(self) -> int:
        return self._stop - self._start

    def block_until_ready(self) -> "SliceResult":
        self._parent.block_until_ready()
        return self

    def _view(self, i):
        full = self._parent._materialize()[i]
        return full[self._start : self._stop]

    @property
    def values(self) -> np.ndarray:
        return self._view(0)

    @property
    def valid(self) -> np.ndarray:
        return self._view(1)

    @property
    def labels(self) -> np.ndarray:
        return self._view(2)


class SVMEngine:
    def __init__(
        self,
        model: CompiledArtifact | ApproxModel,
        exact: SVMModel | None = None,
        *,
        allow_fallback: bool = True,
        mesh: Mesh | None = None,
        head_mesh: Mesh | None = None,
        device=None,
        min_bucket: int = 32,
        max_batch: int = 8192,
        tile_config: TileConfig | None = None,
    ):
        if min_bucket & (min_bucket - 1) or max_batch & (max_batch - 1):
            raise ValueError("min_bucket and max_batch must be powers of two")
        if isinstance(model, CompiledArtifact):
            self.artifact = model
            self.approx = None                 # pre-families accessor
        elif isinstance(model, ApproxModel):
            self.artifact = families.maclaurin.from_approx(model)
            self.approx = model
        else:
            raise TypeError(
                f"SVMEngine serves a CompiledArtifact (or a legacy "
                f"ApproxModel), got {type(model).__name__}"
            )
        self._family = families.get_family(self.artifact.family)
        self.family = self.artifact.family
        self.dtype = self.artifact.dtype      # weight storage: float32 / int8
        self.exact = exact
        self.multiclass = self.artifact.multiclass
        self.num_heads = self.artifact.num_heads
        self.d = self.artifact.d
        self.allow_fallback = allow_fallback and exact is not None
        self.min_bucket = min_bucket
        self.max_batch = max_batch
        self.tile_config = tile_config
        self.bucket_configs: dict[int, TileConfig] = {}
        self.stats = EngineStats()
        self._trace_lock = threading.Lock()   # guards bucket_configs
        self._span_names: dict[tuple[int, str], tuple[str, str, str]] = {}
        self._device = device                 # replica pinning (scale-out)
        self.head_mesh = head_mesh

        # The artifact's arrays are closed over -> baked into the executable
        # as constants; only the padded batch is an argument. Under a
        # head_mesh the heads are padded up to the mesh axis size and the
        # family's sharded scorer partitions them across devices; the
        # padded artifact is engine-internal (padding would change the
        # content digest) and ``num_heads`` keeps the REAL head count —
        # ``_finalize`` slices the score columns back down.
        if head_mesh is not None:
            pad = getattr(self._family, "pad_heads", None)
            sharded = getattr(self._family, "score_sharded", None)
            if pad is None or sharded is None:
                raise NotImplementedError(
                    f"family {self.family!r} has no head-sharded serving path"
                )
            shards = head_mesh.shape[head_mesh.axis_names[0]]
            self._serve_artifact = pad(self.artifact, shards)
        else:
            self._serve_artifact = self.artifact
        artifact = self._serve_artifact

        def _step(Zp):
            # Runs once per bucket (at trace time): resolve this bucket's
            # tuned tile sizes, so warmup() precompiles tuned variants.
            cfg = self._resolve_tile_config(Zp.shape[0])
            if head_mesh is not None:
                scores, valid_row = self._family.score_sharded(
                    artifact, Zp, mesh=head_mesh, config=cfg
                )
            else:
                scores, valid_row = self._family.score(artifact, Zp, config=cfg)
            return scores, valid_row, _labels(scores, self.multiclass)  # fused

        self._step = jax.jit(_step)

        # The exact model (fallback and degraded rows): device arrays
        # handed to the shared exact programs on every call, never baked
        # into an executable (``_exact_programs``).
        self._backend = backend.resolve()
        self._exact_scores, self._exact_step = _exact_programs(mesh)
        self._exact_weights = (self._exact_arrays(exact, mesh)
                               if exact is not None else None)

    # ---------------------------------------------------------- tile tuning

    def _resolve_tile_config(self, bucket: int) -> TileConfig:
        """The TileConfig this shape bucket's compiled step uses.

        Explicit ``tile_config`` pins every bucket; otherwise the tuning
        registry is consulted for the FAMILY's serving kernel and this
        bucket's shape key (``quadform``/(d, K, bucket) for the quadratic
        forms, ``rff_score``/(d, F, bucket) for fourier) — a measured
        entry from the checked-in table (written by the serving-latency
        block sweep) or the kernel default. block_n is clamped to the
        bucket so tiny buckets never pad up to a full default tile.
        """
        with self._trace_lock:
            cached = self.bucket_configs.get(bucket)
            if cached is not None:
                return cached
            if self.tile_config is not None:
                base = self.tile_config
            else:
                kernel, key = self._family.tile_lookup(self.artifact, bucket)
                base = tuning.lookup(kernel, key)
            cfg = base.clamp_block_n(bucket)
            self.bucket_configs[bucket] = cfg
            self.stats.record_compile()       # runs at trace time only
            return cfg

    # ------------------------------------------------------------- fast path

    def _put(self, buf: np.ndarray):
        """Host batch -> device array, honoring the replica's pinned device."""
        if self._device is not None:
            return jax.device_put(buf, self._device)
        return jnp.asarray(buf)

    def _stage_names(self, bkt: int, kind: str):
        """Span names of one chunk's three stages, built once per bucket;
        Nones unless profiling is on. ``kind`` is "step" (pad, put, the
        fast step), "step_exact" (pad, put, the degraded step) or
        "fallback" (the device gather, no put, the exact call)."""
        if _profile_annotation is None:
            return _NO_NAMES
        names = self._span_names.get((bkt, kind))
        if names is None:
            if kind == "fallback":
                names = (f"svm_engine.fallback.gather/b{bkt}", None,
                         f"svm_engine.fallback.step/b{bkt}")
            else:
                step = (f"svm_engine.step_exact/b{bkt}" if kind == "step_exact"
                        else f"svm_engine.step/{self.family}/b{bkt}")
                names = (f"svm_engine.pad/b{bkt}", f"svm_engine.put/b{bkt}", step)
            self._span_names[(bkt, kind)] = names
        return names

    def _enqueue(self, Z, step, *, exact: bool, owned: bool):
        """Copy each ``max_batch`` chunk of ``Z`` into a staging buffer of
        its bucket's size (padding rows zeroed), copy that to the device
        and enqueue ``step`` on it; returns (each chunk's device rows, the
        host arrays sent, chunks, the number of chunks sent without a
        staging copy).

        The staging buffer keeps the rows' memory order, so the copy is a
        straight one. A column-major batch (what ``np.asarray`` of an
        array on the TPU gives) staged row-major would cost a transpose on
        the host, while the transfer lays the rows out for the device
        either way. The staging copy is also what lets a caller reuse its
        array as soon as ``submit`` returns. The fallback re-scores from
        the device rows, never from a host array.

        With ``owned`` the rows are the engine's once the call returns, so
        a chunk that fills its bucket and is contiguous (row- or column-
        major) goes to the device as it is: no padding rows, the same
        values in the same order."""
        Z = np.asarray(Z, dtype=np.float32)
        if Z.ndim != 2 or Z.shape[1] != self.d:
            raise ValueError(f"expected (n, {self.d}) batch, got {Z.shape}")
        xs, sent, chunks, unstaged = [], [], [], 0
        for start in range(0, max(Z.shape[0], 1), self.max_batch):
            rows = Z[start : start + self.max_batch]
            m = rows.shape[0]
            bkt = bucket_size(m, self.min_bucket, self.max_batch)
            pad, put, run = self._stage_names(bkt, "step_exact" if exact else "step")
            if owned and m == bkt and (rows.flags.c_contiguous
                                       or rows.flags.f_contiguous):
                buf = rows
                unstaged += 1
            else:
                with _annotate(pad):
                    buf = np.empty_like(rows, shape=(bkt, self.d))
                    buf[:m] = rows
                    buf[m:] = 0
            with _annotate(put):
                x = self._put(buf)
            with _annotate(run):
                out = step(x)
            xs.append(x)
            sent.append(buf)
            chunks.append((out, m))
        return xs, sent, chunks, unstaged

    def submit(self, Z, *, owned: bool = False) -> EngineResult:
        """Enqueue one batch; returns without waiting for device compute.

        The caller may write to ``Z`` as soon as this returns: each chunk
        is staged in a buffer of the engine's. ``owned=True`` promises
        instead that nothing writes to ``Z`` after the call (the micro-
        batcher passes it for a request's admitted array alone); then a
        chunk that fills its bucket and is contiguous skips the staging
        copy (``EngineStats.unstaged_chunks``). The results are the same
        either way."""
        xs, sent, chunks, unstaged = self._enqueue(Z, self._step, exact=False,
                                                   owned=owned)
        self.stats.record_batch(sum(m for _, m in chunks),
                                [(c[0][0].shape[0], c[1]) for c in chunks],
                                unstaged)
        # the device rows are only needed to re-score bound-violating rows;
        # don't pin them for every deferred batch when no fallback can happen.
        return EngineResult(self, xs if self.allow_fallback else None, chunks,
                            sent)

    @property
    def exact_available(self) -> bool:
        """True when an exact model was published (``submit_exact`` works)."""
        return self._exact_weights is not None

    def submit_exact(self, Z, *, owned: bool = False) -> EngineResult:
        """Score ``Z`` entirely through the exact streaming ``rbf_pred``
        path — the circuit breaker's graceful-degradation target.

        Same deferred-sync ``EngineResult`` surface as ``submit`` (the
        micro-batcher's scatter works unchanged) with every row's
        ``valid`` False: the rows were exact-served, not approximated.
        Batches are bucket-padded like the fast path so degraded serving
        keeps the bounded-compile property (one slow variant per bucket,
        not per batch shape). Requires an exact model. ``owned`` is
        ``submit``'s.
        """
        if self._exact_weights is None:
            raise RuntimeError("submit_exact needs an exact model (none given)")
        _, _, chunks, unstaged = self._enqueue(Z, self._slow_step, exact=True,
                                               owned=owned)
        self.stats.record_degraded(sum(m for _, m in chunks), unstaged)
        return EngineResult(self, None, chunks)   # exact already: no re-score

    def predict(self, Z) -> tuple[np.ndarray, np.ndarray]:
        """Synchronous: (decision values, used_fast_path bool mask)."""
        r = self.submit(Z)
        return r.values, r.valid

    def predict_labels(self, Z) -> np.ndarray:
        """{-1, +1} (binary) or class indices (multiclass)."""
        return self.submit(Z).labels

    def bucket_for(self, n: int) -> int:
        """The padded bucket a batch of ``n`` rows dispatches into —
        lets the scheduler stamp engine-step spans with the bucket and
        its resolved ``TileConfig`` without re-deriving the policy."""
        return bucket_size(max(int(n), 1), self.min_bucket, self.max_batch)

    def jit_cache_size(self) -> int:
        """Number of compiled step variants (== buckets seen); bounded by
        log2(max_batch / min_bucket) + 1 by construction."""
        probe = getattr(self._step, "_cache_size", None)  # private jax API
        if probe is not None:
            return probe()
        return len(self.stats.bucket_hits)                # buckets == variants

    def warmup(self, batch_sizes=None) -> int:
        """Pre-compile every bucket a production stream can hit.

        Warmup traffic does not pollute the serving statistics (only the
        bucket histogram keeps its entries, so jit_cache_size stays
        truthful on jax versions without the cache probe).
        """
        if batch_sizes is None:
            batch_sizes, b = [], self.min_bucket
            while b <= self.max_batch:
                batch_sizes.append(b)
                b *= 2
        saved = self.stats
        self.stats = EngineStats(bucket_hits=dict(saved.bucket_hits))
        try:
            for n in batch_sizes:
                self.submit(np.zeros((n, self.d), np.float32)).block_until_ready()
        finally:
            saved.bucket_hits = self.stats.bucket_hits
            saved.compiled_steps += self.stats.compiled_steps  # traces are real
            self.stats = saved
        return self.jit_cache_size()

    # ------------------------------------------------------------- slow path

    def _exact_arrays(self, exact: SVMModel, mesh: Mesh | None) -> tuple:
        """(X, alpha_y as (K, n_sv), gamma, b as (K,)) on the device(s) the
        exact path runs on. Without a mesh they live on this engine's
        device, so a replica's fallback and degraded rows are scored where
        its fast path runs. With a mesh the SVs are sharded over its first
        axis, padded with alpha = 0 rows, which contribute exactly 0."""
        ay = np.asarray(exact.alpha_y, np.float32)
        ay = ay[None, :] if ay.ndim == 1 else ay
        arrays = (np.asarray(exact.X, np.float32), ay,
                  np.asarray(exact.gamma, np.float32),
                  np.asarray(exact.b, np.float32).reshape(-1))
        if mesh is None:
            return tuple(self._put(a) for a in arrays)
        X, ay, gamma, b = arrays
        axis = mesh.axis_names[0]
        pad = (-X.shape[0]) % mesh.shape[axis]
        arrays = (np.pad(X, ((0, pad), (0, 0))), np.pad(ay, ((0, 0), (0, pad))), gamma, b)
        specs = (P(axis, None), P(None, axis), P(), P())
        return tuple(jax.device_put(a, NamedSharding(mesh, spec))
                     for a, spec in zip(arrays, specs))

    def _slow(self, Zb):
        """(m, K) exact scores of the device rows ``Zb``."""
        return self._exact_scores(Zb, *self._exact_weights, backend_name=self._backend)

    def _slow_step(self, Zp):
        """The degraded-mode step (circuit breaker open): the exact
        expansion shaped like ``_step``, with every row's valid False."""
        return self._exact_step(Zp, *self._exact_weights, backend_name=self._backend,
                                multiclass=self.multiclass)

    # ----------------------------------------------------------- materialize

    def _rescore(self, xs: list, chunks, idx: np.ndarray) -> np.ndarray:
        """(len(idx), K) exact scores of the result's rows ``idx``, read
        from ``xs``, the device rows the fast step ran on.

        At most ``max_batch`` rows per exact call, as on the fast path. A
        call whose rows are all of one chunk's runs on that chunk's array
        as it is: its bucket is the call's, so there is no gather and no
        copy. Any other call's rows are gathered on the device into an
        array of their bucket's size (tail rows zero), one ``take`` per
        chunk they come from, so one exact program per bucket compiles."""
        starts = np.cumsum([0] + [m for _, m in chunks])
        calls, in_place = [], 0
        for lo in range(0, len(idx), self.max_batch):
            part = idx[lo : lo + self.max_batch]
            m = part.shape[0]
            bkt = bucket_size(m, self.min_bucket, self.max_batch)
            gather, _, run = self._stage_names(bkt, "fallback")
            owner = np.searchsorted(starts, part, side="right") - 1
            c = owner[0]
            if owner[-1] == c and m == starts[c + 1] - starts[c]:
                x = xs[c]                   # every row of chunk c, in order
                in_place += 1
            else:
                with _annotate(gather):
                    x = None
                    for c in np.unique(owner):
                        mine = owner == c
                        local = np.full(bkt, xs[c].shape[0], np.int32)  # zero rows
                        local[:m][mine] = part[mine] - starts[c]
                        rows = _gather_rows(xs[c], local)
                        x = rows if x is None else x + rows     # + 0: exact
            with _annotate(run):
                calls.append((self._slow(x), m))
        self.stats.record_fallback(len(idx), len(calls), in_place)
        with _annotate("svm_engine.fallback.sync"):       # device wait + D2H
            return np.concatenate([np.asarray(out)[:m] for out, m in calls])

    def _finalize(self, xs: list | None, chunks):
        """One host sync per result: concat chunks, slice padding, patch
        bound-violating rows through the exact path, from ``xs``, each
        chunk's device rows."""
        with _annotate("svm_engine.sync"):            # device wait + D2H
            scores = np.concatenate(
                [np.asarray(out[0])[:m] for out, m in chunks]
            ) if chunks else np.zeros((0, self.num_heads), np.float32)
            valid = np.concatenate([np.asarray(out[1])[:m] for out, m in chunks]) \
                if chunks else np.zeros((0,), bool)
            labels = np.concatenate([np.asarray(out[2])[:m] for out, m in chunks]) \
                if chunks else np.zeros((0,), np.int32)
        if scores.shape[1] != self.num_heads:
            # head-sharded serving pads K up to the mesh axis size; the
            # padding heads are argmax-neutral, so labels are already
            # correct — only the score columns need slicing back down.
            scores = np.ascontiguousarray(scores[:, : self.num_heads])

        if xs is not None and self.allow_fallback and not valid.all():
            idx = np.nonzero(~valid)[0]
            with _annotate("svm_engine.fallback"):
                exact_scores = self._rescore(xs, chunks, idx)
            scores[idx] = exact_scores
            if self.multiclass:
                labels[idx] = exact_scores.argmax(axis=-1)
            else:
                labels[idx] = np.where(exact_scores[:, 0] >= 0, 1, -1)

        values = scores if self.multiclass else scores[:, 0]
        return values, valid, labels
