"""``CompiledArtifact`` — the train → compile → serve seam.

Every approximation family (maclaurin, poly2, fourier, ...) compiles an
exact ``SVMModel`` into one of these: a named bag of device arrays plus
JSON-able metadata. The artifact is the ONLY thing the serving stack
needs — no training-side objects (``SVMModel``, solver state, rngs)
survive compilation, so a server process can ``CompiledArtifact.load``
an ``.npz`` file and serve it without importing any training code.

Design points:

  * **Pytree-registered.** Arrays are the children (sorted by key so the
    flatten order is stable); ``(family, keys, meta)`` is the aux data.
    Artifacts therefore pass through ``jax.jit`` / ``jax.device_put`` /
    donation like any model pytree.
  * **Versioned npz.** ``save``/``load`` speak a plain ``.npz`` with one
    extra ``__artifact__`` member holding the JSON header (format
    version, family name, meta). ``load`` refuses future format
    versions instead of mis-parsing them.
  * **Deterministic bytes.** ``save`` writes zip members itself with
    pinned timestamps/permissions (ZIP_STORED), so compiling the same
    model with the same seed yields BIT-IDENTICAL files across
    processes — artifact stores can be content-addressed and diffed.

Family modules register themselves in ``repro.core.families.FAMILIES``;
scoring dispatches on ``artifact.family`` (see ``score_artifact``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import io
import json
import zipfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rbf import rbf_kernel

Array = jax.Array

# Bump when the on-disk layout changes incompatibly. Readers accept
# anything <= their own version and reject newer files loudly.
# v2: quantized variants — int8 weight arrays with per-group f32 scales,
#     ``dtype`` in the meta (absent in v1 files => "float32").
ARTIFACT_FORMAT_VERSION = 2

_HEADER_MEMBER = "__artifact__"

# Bias given to validity-neutral padding heads (head-sharded serving
# pads K up to the mesh axis size): exp-enveloped scores are O(|c|+|v|+|M|)
# magnitudes, so a -1e30 bias can never win an argmax, and padding heads
# carry msq = 0, which satisfies Eq 3.11 for every row.
PAD_HEAD_BIAS = -1e30


@dataclasses.dataclass(frozen=True, eq=False)
class CompiledArtifact:
    """One servable model: ``family`` tag, device arrays, JSON-able meta.

    ``meta`` always carries ``format_version``, ``d`` (feature dim),
    ``num_heads`` (K) and ``multiclass``; families add their own keys
    (error-bound constants, held-out error estimates, rng seeds, ...).
    """

    family: str
    arrays: dict[str, Array]
    meta: dict

    # ------------------------------------------------------------ conveniences

    @property
    def d(self) -> int:
        return int(self.meta["d"])

    @property
    def num_heads(self) -> int:
        return int(self.meta["num_heads"])

    @property
    def multiclass(self) -> bool:
        return bool(self.meta["multiclass"])

    @property
    def dtype(self) -> str:
        """Weight storage dtype: "float32" or "int8" (v1 files: float32)."""
        return self.meta.get("dtype", "float32")

    def nbytes(self) -> int:
        """In-memory size of the servable arrays (Table-3 accounting)."""
        return sum(a.size * a.dtype.itemsize for a in self.arrays.values())

    def with_meta(self, **updates) -> "CompiledArtifact":
        """Functional meta update (arrays shared, not copied)."""
        return CompiledArtifact(self.family, self.arrays, {**self.meta, **updates})

    # ------------------------------------------------------------- persistence

    def to_bytes(self) -> bytes:
        """The deterministic versioned ``.npz`` bytes ``save`` writes.

        Same model + seed ⇒ bit-identical bytes across processes (pinned
        zip metadata), so these bytes — not the object identity — are the
        canonical identity of a compiled model. ``digest()`` hashes them.
        """
        header = json.dumps(
            {
                "format_version": ARTIFACT_FORMAT_VERSION,
                "family": self.family,
                "meta": self.meta,
                "keys": sorted(self.arrays),
            },
            sort_keys=True,
        ).encode()
        members = {_HEADER_MEMBER: np.frombuffer(header, dtype=np.uint8)}
        for name in sorted(self.arrays):
            members[name] = np.ascontiguousarray(self.arrays[name])
        out = io.BytesIO()
        with zipfile.ZipFile(out, "w", zipfile.ZIP_STORED) as zf:
            for name, arr in members.items():
                buf = io.BytesIO()
                np.lib.format.write_array(buf, arr, allow_pickle=False)
                _write_member(zf, name + ".npy", buf.getvalue())
        return out.getvalue()

    def digest(self) -> str:
        """SHA-256 hex digest of ``to_bytes()`` — the content address.

        save → load → save round-trips to the SAME digest (tested), so an
        artifact registry can dedupe identical compiles and key a store on
        the digest regardless of which process produced the file.
        """
        return hashlib.sha256(self.to_bytes()).hexdigest()

    def save(self, path: str) -> str:
        """Write a deterministic versioned ``.npz``; returns ``path``."""
        with open(path, "wb") as f:
            f.write(self.to_bytes())
        return path

    @classmethod
    def load(cls, path: str) -> "CompiledArtifact":
        """Read an artifact written by ``save`` (any version <= current)."""
        with np.load(path, allow_pickle=False) as z:
            if _HEADER_MEMBER not in z.files:
                raise ValueError(f"{path} is not a CompiledArtifact npz "
                                 f"(missing {_HEADER_MEMBER!r} member)")
            header = json.loads(bytes(z[_HEADER_MEMBER]).decode())
            version = header.get("format_version")
            if not isinstance(version, int) or version > ARTIFACT_FORMAT_VERSION:
                raise ValueError(
                    f"artifact format version {version!r} is newer than this "
                    f"reader (supports <= {ARTIFACT_FORMAT_VERSION}); "
                    f"upgrade repro to load {path}"
                )
            arrays = {k: jnp.asarray(z[k]) for k in header["keys"]}
        return cls(family=header["family"], arrays=arrays, meta=header["meta"])


def _write_member(zf: zipfile.ZipFile, name: str, payload: bytes) -> None:
    """One zip member with pinned metadata (the determinism guarantee)."""
    info = zipfile.ZipInfo(name, date_time=(1980, 1, 1, 0, 0, 0))
    info.compress_type = zipfile.ZIP_STORED
    info.external_attr = 0o644 << 16
    zf.writestr(info, payload)


# ------------------------------------------------------------------ pytree


def _flatten(art: CompiledArtifact):
    keys = tuple(sorted(art.arrays))
    children = tuple(art.arrays[k] for k in keys)
    aux = (art.family, keys, json.dumps(art.meta, sort_keys=True))
    return children, aux


def _unflatten(aux, children):
    family, keys, meta_json = aux
    return CompiledArtifact(
        family=family, arrays=dict(zip(keys, children)), meta=json.loads(meta_json)
    )


jax.tree_util.register_pytree_node(CompiledArtifact, _flatten, _unflatten)


def base_meta(
    *, d: int, num_heads: int, multiclass: bool, dtype: str = "float32", **extra
) -> dict:
    """The meta keys every family must provide, plus family extras."""
    return {
        "format_version": ARTIFACT_FORMAT_VERSION,
        "d": int(d),
        "num_heads": int(num_heads),
        "multiclass": bool(multiclass),
        "dtype": str(dtype),
        **extra,
    }


def stack_heads(svm) -> tuple[Array, Array, int, bool]:
    """View an ``SVMModel``'s (alpha_y, b) as a K-head stack.

    Binary models store ``alpha_y`` as (n_sv,); OvR ensembles (from
    ``repro.svm.multiclass.train_one_vs_rest``) as (K, n_sv) with b (K,).
    Every family compiles the K-stacked view so serving is uniformly
    multi-head (K = 1 is just the smallest stack).
    """
    ay = svm.alpha_y
    multiclass = ay.ndim == 2
    ay2 = ay if multiclass else ay[None, :]
    b = jnp.reshape(svm.b, (ay2.shape[0],))
    return ay2, b, ay2.shape[0], multiclass


def exact_scores(svm, Z) -> Array:
    """(n, K) exact decision values of every head of ``svm`` on ``Z``.

    The reference every approximation is judged against (``compile_model``,
    fourier's held-out verdict, the ``DriftGuard`` canary), so it runs in
    f32 at "highest" matmul precision: a TPU's default f32 matmul rounds
    its operands to bf16, and with a small gamma every kernel value sits
    near 1, where bf16 keeps two or three digits.
    """
    ay2, b, _, _ = stack_heads(svm)
    with jax.default_matmul_precision("highest"):
        kmat = rbf_kernel(jnp.asarray(Z, jnp.float32), svm.X, svm.gamma)
        return kmat @ ay2.T + b[None, :]
