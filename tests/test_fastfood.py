"""The Fastfood fast path (ISSUE 8): FWHT kernel Pallas-vs-XLA agreement
across non-power-of-two d (the padding path), int8 structured artifacts
(layout, >= 3x serialization win, argmax parity, digest determinism,
pad-head neutrality), the fwht tuning families surviving table
validation, and the structured roofline prior that lets compile_model
rank Fastfood against dense RFF."""

import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import backend, gamma_max
from repro.core.families import fourier, maclaurin, quantize
from repro.core.rbf import SVMModel
from repro.kernels.common import cost, tuning
from repro.kernels.common.config import TileConfig
from repro.kernels.fwht import (
    fastfood_project,
    fastfood_score_pallas,
    fastfood_score_q8_pallas,
    fastfood_score_q8_ref,
    fastfood_score_ref,
    fwht,
    fwht_xla,
)
from repro.serve.svm_engine import SVMEngine


def _svm_mc(seed=0, d=8, n_sv=40, k=4, scale=0.5):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * scale
    gamma = float(gamma_max(jnp.asarray(X))) * 0.8
    ay = rng.standard_normal((k, n_sv)).astype(np.float32) * 0.5
    b = (rng.standard_normal(k) * 0.1).astype(np.float32)
    return SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
                    b=jnp.asarray(b), gamma=jnp.float32(gamma))


def _operands(rng, n, d, stacks, k):
    """Random Fastfood operands at d' = next pow2 >= d."""
    dd = 1 << max(1, (d - 1).bit_length())
    f = stacks * dd
    return dict(
        Z=jnp.asarray(rng.standard_normal((n, d)).astype(np.float32)),
        B=jnp.asarray(rng.choice(np.float32([-1, 1]), (stacks, dd))),
        G=jnp.asarray(rng.standard_normal((stacks, dd)).astype(np.float32)),
        perm=jnp.asarray(
            np.stack([rng.permutation(dd) for _ in range(stacks)]).astype(np.int32)
        ),
        scale=jnp.asarray(
            (rng.standard_normal((stacks, dd)) * 0.1).astype(np.float32)
        ),
        phase=jnp.asarray(rng.uniform(0, 2 * np.pi, f).astype(np.float32)),
        weights=jnp.asarray(
            (rng.standard_normal((k, f)) * 0.05).astype(np.float32)
        ),
        bias=jnp.asarray(rng.standard_normal(k).astype(np.float32)),
    )


# ----------------------------------------------------------- transform math


def test_fwht_matches_hadamard_matrix():
    # Sylvester construction is the ground truth for the butterfly loop.
    d = 16
    H = np.array([[1.0]])
    while H.shape[0] < d:
        H = np.block([[H, H], [H, -H]])
    x = np.random.default_rng(0).standard_normal((5, d)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(fwht(jnp.asarray(x))), x @ H.T, rtol=1e-5, atol=1e-4
    )


@pytest.mark.parametrize("d", [1, 2, 8, 64, 1024])
def test_fwht_xla_matches_butterfly(d):
    # The Kronecker-GEMM schedule (what fastfood_project dispatches under
    # XLA) must agree with the butterfly (what the Pallas kernel unrolls)
    # at every width class: trivial, odd-k (unbalanced split), balanced.
    x = np.random.default_rng(d).standard_normal((7, d)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(fwht_xla(jnp.asarray(x))), np.asarray(fwht(jnp.asarray(x))),
        rtol=1e-5, atol=1e-4,
    )


@pytest.mark.parametrize("d", [128, 512])
def test_kernel_transform_pieces_are_bit_exact(d):
    # Inside the kernel the butterfly is lane rotations + a select and the
    # permutation a split-bf16 one-hot GEMM (what Mosaic lowers); both must
    # reproduce ref.fwht and jnp.take bit for bit.
    from jax.experimental import pallas as pl

    from repro.kernels.fwht.kernel import _fwht_lanes, _permute_lanes

    rng = np.random.default_rng(d)
    x = jnp.asarray(rng.standard_normal((16, d)).astype(np.float32) * 3.0)
    perm = jnp.asarray(rng.permutation(d).astype(np.int32)[None, :])

    def body(x_ref, p_ref, h_ref, g_ref):
        h_ref[...] = _fwht_lanes(x_ref[...])
        g_ref[...] = _permute_lanes(x_ref[...], p_ref, 0)

    h, g = pl.pallas_call(
        body, out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32)] * 2,
        interpret=True,
    )(x, perm)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(fwht(x)))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(x)[:, np.asarray(perm)[0]])


def test_fastfood_project_pads_nonpow2_d_exactly():
    # Zero-padding d -> d' must equal projecting the explicitly padded Z.
    rng = np.random.default_rng(1)
    ops = _operands(rng, 7, 20, 2, 3)
    dd = ops["B"].shape[1]
    Zp = jnp.pad(ops["Z"], ((0, 0), (0, dd - 20)))
    a = fastfood_project(ops["Z"], ops["B"], ops["G"], ops["perm"], ops["scale"])
    b = fastfood_project(Zp, ops["B"], ops["G"], ops["perm"], ops["scale"])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ Pallas-vs-XLA parity


@pytest.mark.parametrize("d", [6, 20, 100])
def test_fastfood_pallas_matches_xla_nonpow2_d(d):
    rng = np.random.default_rng(d)
    ops = _operands(rng, 33, d, 3, 5)  # n=33: exercises row-tile padding
    ref = fastfood_score_ref(**ops)
    got = fastfood_score_pallas(
        **ops, config=TileConfig(block_n=16), interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-4
    )


@pytest.mark.parametrize("d", [6, 20, 100])
def test_fastfood_q8_pallas_matches_xla_nonpow2_d(d):
    rng = np.random.default_rng(100 + d)
    ops = _operands(rng, 21, d, 2, 6)
    stacks, k = 2, 6
    q = dict(
        Z=ops["Z"],
        b_q=ops["B"].astype(jnp.int8),
        g_q=jnp.clip(jnp.round(ops["G"] / 0.02), -127, 127).astype(jnp.int8),
        perm=ops["perm"],
        s_q=jnp.clip(jnp.round(ops["scale"] / 0.002), -127, 127).astype(jnp.int8),
        stack_scale=jnp.full((stacks,), 0.02 * 0.002, jnp.float32),
        phase=ops["phase"],
        weights_q=jnp.clip(
            jnp.round(ops["weights"] / 0.001), -127, 127
        ).astype(jnp.int8),
        wt_scale=jnp.full((k,), 0.001, jnp.float32),
        bias=ops["bias"],
    )
    ref = fastfood_score_q8_ref(**q)
    got = fastfood_score_q8_pallas(
        **q, config=TileConfig(block_n=8), interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-4
    )


def test_backend_dispatch_agrees_across_backends():
    rng = np.random.default_rng(5)
    ops = _operands(rng, 17, 20, 2, 4)
    prev = backend.set_backend("xla")
    try:
        sx = backend.fastfood_score(**ops)
        backend.set_backend("pallas")
        sp = backend.fastfood_score(**ops)
    finally:
        backend.set_backend(prev)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sx), atol=1e-4)


# -------------------------------------------------------- int8 artifacts


def test_int8_fastfood_artifact_contract():
    m = _svm_mc(7, d=100, n_sv=60, k=10)
    f32 = fourier.compile(m, num_features=2048, structured=True, seed=3)
    q8 = fourier.compile(
        m, num_features=2048, structured=True, dtype="int8", seed=3
    )
    # layout: every F- or K-scaling array narrowed
    a = q8.arrays
    assert a["ff_b"].dtype == jnp.int8 and a["ff_g"].dtype == jnp.int8
    assert a["ff_scale"].dtype == jnp.int8
    assert a["ff_perm"].dtype == jnp.int16
    assert a["phase"].dtype == jnp.float16
    assert a["weights"].dtype == jnp.int8
    # >= 3x smaller serialized (ISSUE 8 acceptance)
    ratio = len(f32.to_bytes()) / len(q8.to_bytes())
    assert ratio >= 3.0, ratio
    # measured quant error rides in the meta
    assert q8.meta["quant_mean_abs_err"] < 0.05
    assert q8.meta["quant_holdout_n"] > 0
    # argmax parity vs the f32 parent on held-out points
    Z = jnp.asarray(fourier.holdout_sample(m, 3, 128))
    s32, _ = fourier.score(f32, Z)
    s8, _ = fourier.score(q8, Z)
    parity = float(np.mean(
        np.argmax(np.asarray(s32), 1) == np.argmax(np.asarray(s8), 1)
    ))
    assert parity >= 0.99, parity
    # distinct content addresses, both serve through the engine
    assert f32.digest() != q8.digest()
    labels = SVMEngine(q8, allow_fallback=False).predict_labels(
        np.asarray(Z[:9])
    )
    assert labels.shape == (9,)


def test_int8_fastfood_digest_deterministic_in_process():
    m = _svm_mc(11, d=20, n_sv=40, k=3)
    d1 = fourier.compile(
        m, num_features=64, structured=True, dtype="int8", seed=5
    ).digest()
    d2 = fourier.compile(
        m, num_features=64, structured=True, dtype="int8", seed=5
    ).digest()
    assert d1 == d2


def test_quantize_signs_and_compact_perm():
    assert quantize.quantize_signs(
        jnp.asarray([[1.0, -1.0]])
    ).dtype == jnp.int8
    with pytest.raises(ValueError, match="sign"):
        quantize.quantize_signs(jnp.asarray([0.5, 1.0]))
    assert quantize.compact_perm(np.arange(64)).dtype == jnp.int16
    assert quantize.compact_perm(np.arange(2**16)).dtype == jnp.int32


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_fastfood_pad_heads_is_argmax_neutral(dtype):
    m = _svm_mc(13, d=20, n_sv=40, k=5)
    art = fourier.compile(
        m, num_features=64, structured=True, dtype=dtype, seed=2
    )
    padded = fourier.pad_heads(art, 4)
    assert padded.meta["padded_heads"] == 8
    Z = jnp.asarray(fourier.holdout_sample(m, 2, 32))
    ref, _ = fourier.score(art, Z)
    got, _ = fourier.score(padded, Z)
    np.testing.assert_allclose(
        np.asarray(got[:, :5]), np.asarray(ref), rtol=1e-5, atol=1e-5
    )
    assert int(np.asarray(got).argmax(axis=1).max()) < 5
    # aligned width is a no-op
    assert fourier.pad_heads(art, 5) is art


@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("variant", ["structured", "dense", "quadform"])
def test_every_variant_serves_a_bucket_without_recompiling(variant, dtype):
    m = _svm_mc(19, d=20, n_sv=40, k=4)
    if variant == "quadform":
        art = maclaurin.compile(m, dtype=dtype)
    else:
        art = fourier.compile(
            m, num_features=64, structured=variant == "structured", dtype=dtype
        )
    eng = SVMEngine(art, allow_fallback=False, min_bucket=32, max_batch=32)
    eng.warmup([32])
    compiled = eng.jit_cache_size()
    Z = np.asarray(fourier.holdout_sample(m, 4, 32))
    for n in (32, 7, 19, 32):
        assert eng.predict(Z[:n])[0].shape == (n, 4)
    assert eng.jit_cache_size() == compiled


# ----------------------------------------------------------- tuning registry


def test_tile_lookup_resolves_fwht_families():
    m = _svm_mc(17, d=20, k=3)
    f32 = fourier.compile(m, num_features=64, structured=True)
    q8 = fourier.compile(m, num_features=64, structured=True, dtype="int8")
    kf, key = fourier.tile_lookup(f32, 256)
    kq, _ = fourier.tile_lookup(q8, 256)
    assert kf == "fwht" and kq == "fwht_q8"
    assert key == tuning.shape_key(d=20, f=64, n=256)
    # both families resolve a default config without raising
    assert tuning.lookup(kf, key).block_n > 0
    assert tuning.lookup(kq, key).block_n > 0


def test_validate_table_drops_unknown_kernel_keeps_fwht():
    # Regression (ISSUE 8 satellite): a table shipped by a NEWER build with
    # kernel families this build doesn't know must warn-and-drop those
    # entries, not break the loader — and the fwht entries this PR ships
    # must survive validation in the current build.
    entry = {"config": {"block_n": 128}, "measured_ms": 0.5}
    table = {
        "version": 1,
        "entries": {"cpu": {
            "fwht": {"d784_f2048_n256": entry},
            "fwht_q8": {"d784_f2048_n256": entry},
            "kernel_from_the_future": {"d8_n32": entry},
        }},
    }
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        clean = tuning.validate_table(table, origin="test")
    assert any("kernel_from_the_future" in str(x.message) for x in w)
    kept = clean["entries"]["cpu"]
    assert set(kept) == {"fwht", "fwht_q8"}
    assert kept["fwht"]["d784_f2048_n256"] == entry
    # the original table is not mutated
    assert "kernel_from_the_future" in table["entries"]["cpu"]


# ------------------------------------------------------------ roofline prior


def test_roofline_structured_prior_undercuts_dense_at_mnist_shape():
    cfg = TileConfig(block_n=256)
    dense = cost.rff_tile_seconds(cfg, n=256, d=784, f=2048, k=10)
    structured = cost.fwht_tile_seconds(cfg, n=256, d=784, f=2048, k=10)
    assert structured < dense
    # int8 streams fewer bytes than f32 in both forms
    assert cost.fwht_tile_seconds(
        cfg, n=256, d=784, f=2048, k=10, weight_bytes=1
    ) <= structured
    # family_candidate_seconds threads structured= through
    fd = cost.family_candidate_seconds(
        "fourier", "float32", n=256, d=784, k=10, num_features=2048
    )
    fs = cost.family_candidate_seconds(
        "fourier", "float32", n=256, d=784, k=10, num_features=2048,
        structured=True,
    )
    assert fs < fd
    # bigger tiles amortize the streamed readout
    assert cost.fwht_tile_seconds(
        TileConfig(block_n=512), n=1024, d=784, f=2048, k=10
    ) < cost.fwht_tile_seconds(
        TileConfig(block_n=64), n=1024, d=784, f=2048, k=10
    )
