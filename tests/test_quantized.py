"""Int8 artifact variants (ISSUE 5): round-trip bit-identity, digest
separation from the f32 parents, the >= 3x serialization win, argmax
parity through the engine, fused-dequant kernel agreement (pallas
interpret vs xla), registry eviction/reload of quantized entries, and
quantization as a first-class candidate axis in compile_model."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import Budget, CompiledArtifact, backend, compile_model, gamma_max
from repro.core.families import FAMILIES, get_family, quantize, score_artifact
from repro.core.families.base import ARTIFACT_FORMAT_VERSION, exact_scores
from repro.core.rbf import SVMModel
from repro.serve import PublishSpec
from repro.serve.svm_engine import SVMEngine

NUM_FEATURES = 256          # small fourier basis keeps the suite fast


def _svm(seed=0, d=8, n_sv=60, heads=None, scale=0.6):
    """Deterministic small model straight from an rng (no training)."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * scale
    gamma = float(gamma_max(jnp.asarray(X))) * 0.8
    if heads is None:
        ay = rng.standard_normal(n_sv).astype(np.float32) * 0.5
        b = jnp.float32(0.1)
    else:
        ay = rng.standard_normal((heads, n_sv)).astype(np.float32) * 0.5
        b = jnp.asarray(0.1 * rng.standard_normal(heads).astype(np.float32))
    return SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
                    b=b, gamma=jnp.float32(gamma))


def _compile_pair(family, m, **opts):
    fam = get_family(family)
    f32 = fam.compile(m, num_features=NUM_FEATURES, **opts)
    q8 = fam.compile(m, num_features=NUM_FEATURES, dtype="int8", **opts)
    return f32, q8


# ------------------------------------------------------------- quantize core


def test_quantize_groups_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 40)).astype(np.float32) * np.logspace(
        -2, 1, 5
    )[:, None].astype(np.float32)
    q, scale = quantize.quantize_groups(x, axis=-1)
    assert np.asarray(q).dtype == np.int8
    assert scale.shape == (5, quantize.num_groups(40))
    back = np.asarray(quantize.dequantize_groups(q, scale))
    # symmetric rounding: per-element error is at most half a step of the
    # element's own group scale
    step = np.repeat(np.asarray(scale), quantize.GROUP_SIZE, axis=-1)[:, :40]
    assert (np.abs(back - x) <= 0.5 * step + 1e-7).all()


def test_quantize_col_groups_scale_layout():
    rng = np.random.default_rng(1)
    M = rng.standard_normal((3, 20, 20)).astype(np.float32)
    q, scale = quantize.quantize_col_groups(M)
    assert q.shape == M.shape and np.asarray(q).dtype == np.int8
    # one scale per (head, column-group): independent of the row axis
    assert scale.shape == (3, quantize.num_groups(20))
    col = np.asarray(quantize.expand_group_scales(scale, 20))
    back = np.asarray(q, np.float32) * col[:, None, :]
    assert np.abs(back - M).max() <= 0.5 * col.max() + 1e-7


def test_quantize_zero_group_is_exact():
    x = np.zeros((2, 32), np.float32)
    q, scale = quantize.quantize_groups(x)
    assert (np.asarray(q) == 0).all()
    assert (np.asarray(scale) == 1.0).all()     # never divides by zero
    assert (np.asarray(quantize.dequantize_groups(q, scale)) == 0).all()


def test_check_dtype_rejects_unknown():
    with pytest.raises(ValueError, match="dtype"):
        quantize.check_dtype("int4")
    with pytest.raises(ValueError, match="dtype"):
        get_family("maclaurin").compile(_svm(0), dtype="fp16")


# ---------------------------------------------------------------- artifacts


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_int8_roundtrip_save_load_serve_bit_identical(family, tmp_path):
    m = _svm(3, d=12, n_sv=50, heads=4)
    _, q8 = _compile_pair(family, m)
    path = str(tmp_path / f"{family}_q8.npz")
    q8.save(path)
    back = CompiledArtifact.load(path)
    assert back.dtype == "int8" and back.meta == q8.meta
    for k in q8.arrays:
        assert back.arrays[k].dtype == q8.arrays[k].dtype
        np.testing.assert_array_equal(np.asarray(back.arrays[k]),
                                      np.asarray(q8.arrays[k]))

    Z = np.random.default_rng(5).standard_normal((33, 12)).astype(np.float32) * 0.3
    e1 = SVMEngine(q8, None, allow_fallback=False)
    e2 = SVMEngine(back, None, allow_fallback=False)
    np.testing.assert_array_equal(e1.predict(Z)[0], e2.predict(Z)[0])
    np.testing.assert_array_equal(e1.predict_labels(Z), e2.predict_labels(Z))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_int8_digest_differs_from_f32_and_is_deterministic(family):
    m = _svm(4, d=10, n_sv=40, heads=3)
    f32, q8 = _compile_pair(family, m)
    assert q8.digest() != f32.digest()
    # recompiling quantizes to bit-identical bytes (content addressing)
    again = get_family(family).compile(m, num_features=NUM_FEATURES, dtype="int8")
    assert again.to_bytes() == q8.to_bytes()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_int8_serializes_3x_smaller(family):
    # sized so the weight payload dominates the constant npz header cost
    m = _svm(6, d=64, n_sv=80, heads=10)
    fam = get_family(family)
    f32 = fam.compile(m, num_features=1024)
    q8 = fam.compile(m, num_features=1024, dtype="int8")
    ratio = len(f32.to_bytes()) / len(q8.to_bytes())
    assert ratio >= 3.0, f"{family}: int8 only {ratio:.2f}x smaller"
    assert q8.nbytes() * 3 <= f32.nbytes()      # in-memory too


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_quant_error_measured_and_reported(family):
    m = _svm(7, d=16, n_sv=60, heads=4)
    f32, q8 = _compile_pair(family, m)
    assert q8.meta["dtype"] == "int8"
    assert q8.meta["quant_mean_abs_err"] <= 0.01
    assert q8.meta["quant_mean_abs_err"] <= q8.meta["quant_max_abs_err"]
    # the reported error reproduces on the same deterministic holdout
    from repro.core.families import fourier

    Z = jnp.asarray(fourier.holdout_sample(m, 0, 256))
    ref, _ = score_artifact(f32, Z)
    got, _ = score_artifact(q8, Z)
    err = np.abs(np.asarray(got) - np.asarray(ref))
    assert np.isclose(err.mean(), q8.meta["quant_mean_abs_err"], rtol=1e-4)
    assert np.isclose(err.max(), q8.meta["quant_max_abs_err"], rtol=1e-4)
    # against the exact expansion, int8 stays within 0.01 of its parent
    exact = np.asarray(exact_scores(m, Z))
    assert np.abs(np.asarray(got) - exact).mean() <= (
        np.abs(np.asarray(ref) - exact).mean() + 0.01
    )
    # and the engine keeps its labels
    e_f32 = SVMEngine(f32, None, allow_fallback=False)
    e_q8 = SVMEngine(q8, None, allow_fallback=False)
    parity = np.mean(e_f32.predict_labels(Z) == e_q8.predict_labels(Z))
    assert parity >= 0.99, f"{family}: label parity {parity}"


def test_v1_artifact_without_dtype_loads_as_float32(tmp_path):
    """Files written before the v2 bump carry no dtype key; they must load
    and identify as float32 (the only thing v1 could contain)."""
    m = _svm(8)
    art = get_family("maclaurin").compile(m)
    meta = {k: v for k, v in art.meta.items() if k != "dtype"}
    v1 = CompiledArtifact(art.family, art.arrays, {**meta, "format_version": 1})
    path = str(tmp_path / "v1.npz")
    v1.save(path)
    back = CompiledArtifact.load(path)
    assert back.meta["format_version"] == 1
    assert back.dtype == "float32"
    assert ARTIFACT_FORMAT_VERSION >= 2


# ------------------------------------------------------------------- engine


def test_engine_int8_argmax_parity_multiclass():
    m = _svm(9, d=32, n_sv=100, heads=8)
    f32, q8 = _compile_pair("maclaurin", m)
    e_f32 = SVMEngine(f32, None, allow_fallback=False)
    e_q8 = SVMEngine(q8, None, allow_fallback=False)
    assert e_q8.dtype == "int8" and e_f32.dtype == "float32"
    Z = np.random.default_rng(10).standard_normal((256, 32)).astype(np.float32) * 0.3
    parity = float(np.mean(e_f32.predict_labels(Z) == e_q8.predict_labels(Z)))
    assert parity >= 0.99, f"argmax parity {parity}"


def test_engine_int8_keeps_row_fallback_contract():
    """Eq 3.11 validity depends only on ||z||^2/gamma/msq, so the int8
    quadform keeps the per-row contract and out-of-envelope rows still
    re-score through the exact path."""
    m = _svm(11, d=8, n_sv=60)
    q8 = get_family("maclaurin").compile(m, dtype="int8")
    eng = SVMEngine(q8, m)
    Z = np.random.default_rng(12).standard_normal((40, 8)).astype(np.float32) * 0.3
    Z[:4] *= 50.0                               # far outside the envelope
    vals, valid = eng.predict(Z)
    assert not valid[:4].any() and valid[4:].all()
    assert eng.stats.fallback_instances == 4


@pytest.mark.parametrize("family,kernel", [
    ("maclaurin", "quadform_q8"),
    ("poly2", "quadform_q8"),
    ("fourier", "rff_score_q8"),
])
def test_tile_lookup_resolves_q8_kernel_family(family, kernel):
    m = _svm(13, d=8, n_sv=30, heads=2)
    f32, q8 = _compile_pair(family, m)
    assert get_family(family).tile_lookup(q8, 256)[0] == kernel
    assert get_family(family).tile_lookup(f32, 256)[0] != kernel
    # the engine resolves a per-bucket config through the q8 family
    eng = SVMEngine(q8, None, allow_fallback=False, min_bucket=32, max_batch=64)
    eng.warmup()
    assert sorted(eng.bucket_configs) == [32, 64]


# ------------------------------------------------------------------ kernels


def test_quadform_q8_pallas_matches_xla():
    rng = np.random.default_rng(14)
    n, d, k = 48, 40, 3
    Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32) * 0.3)
    M = rng.standard_normal((k, d, d)).astype(np.float32) * 0.05
    M_q, m_scale = quantize.quantize_col_groups(M)
    col = quantize.expand_group_scales(m_scale, d)
    V = jnp.asarray(rng.standard_normal((k, d)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal(k).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(k).astype(np.float32))
    g = jnp.full((k,), 0.05, jnp.float32)
    msq = jnp.full((k,), 2.0, jnp.float32)

    prev = backend.set_backend("xla")
    try:
        sx, zx, vx = backend.quadform_heads_q8(Z, M_q, col, V, c, b, g, msq)
        backend.set_backend("pallas")
        sp, zp, vp = backend.quadform_heads_q8(Z, M_q, col, V, c, b, g, msq)
    finally:
        backend.set_backend(prev)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sx), atol=1e-5)
    np.testing.assert_allclose(np.asarray(zp), np.asarray(zx), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(vx))


def test_rff_q8_pallas_matches_xla():
    rng = np.random.default_rng(15)
    n, d, f, k = 40, 24, 200, 3
    Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32) * 0.3)
    W_q, w_s = quantize.quantize_rows(
        rng.standard_normal((f, d)).astype(np.float32)
    )
    wt_q, wt_s = quantize.quantize_rows(
        rng.standard_normal((k, f)).astype(np.float32) * 0.01
    )
    ph = jnp.asarray(rng.uniform(0, 2 * np.pi, f).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(k).astype(np.float32))

    prev = backend.set_backend("xla")
    try:
        sx = backend.rff_score_q8(Z, W_q, w_s, ph, wt_q, wt_s, b)
        backend.set_backend("pallas")
        sp = backend.rff_score_q8(Z, W_q, w_s, ph, wt_q, wt_s, b)
    finally:
        backend.set_backend(prev)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sx), atol=1e-5)


# ----------------------------------------------------------------- registry


def test_registry_evicts_and_reloads_quantized_artifact(tmp_path):
    from repro.serve.runtime import ArtifactRegistry

    m = _svm(16, d=24, n_sv=80, heads=4)
    f32, q8 = _compile_pair("maclaurin", m)
    path = str(tmp_path / "q8.npz")
    q8.save(path)

    reg = ArtifactRegistry(
        memory_budget_bytes=f32.nbytes() + q8.nbytes() // 2,
        warmup_on_load=False,
    )
    d_q8 = reg.add_file(path, alias="det-int8")
    d_f32 = reg.register(f32, PublishSpec(alias="det-f32"))
    assert d_q8 == q8.digest() != d_f32       # variants are distinct entries

    Z = np.random.default_rng(17).standard_normal((16, 24)).astype(np.float32) * 0.3
    _, eng_q8 = reg.get_engine("det-int8")
    before = eng_q8.predict(Z)[0]
    # touching the f32 entry busts the budget -> the int8 engine (LRU) drops
    reg.get_engine("det-f32")
    snap = reg.snapshot()
    assert snap["evictions"] >= 1 and snap["loaded"] == 1
    # next use transparently reloads from the file to identical results
    _, eng_again = reg.get_engine("det-int8")
    assert eng_again is not eng_q8
    np.testing.assert_array_equal(eng_again.predict(Z)[0], before)
    assert eng_again.dtype == "int8"


# ------------------------------------------------------------- compile_model


def test_compile_model_treats_int8_as_candidates():
    m = _svm(18, d=10, n_sv=60, heads=3)
    art = compile_model(m, Budget(max_err=0.05, metric="mean_abs"), seed=2)
    rep = art.meta["compile_report"]
    assert rep["chosen_dtype"] == art.dtype
    rows = {(r["family"], r.get("dtype")) for r in rep["families"]}
    assert rows == {(f, dt) for f in FAMILIES for dt in ("float32", "int8")}
    q8_rows = [r for r in rep["families"] if r.get("dtype") == "int8"]
    assert all("quant_mean_abs_err" in r for r in q8_rows)
    # the artifact actually serves
    eng = SVMEngine(art, m)
    assert eng.predict_labels(np.asarray(m.X[:9])).shape == (9,)


def test_compile_model_enumerates_structured_fourier_int8():
    # Regression (ISSUE 8): the structured-Fastfood int8 candidate used to
    # be a typed-skip row; it is now a first-class measured candidate.
    m = _svm(19, d=6, n_sv=30)
    art = compile_model(
        m, Budget(max_err=10.0), seed=1,
        families=("fourier",),
        family_opts={"fourier": {"structured": True, "num_features": 32}},
    )
    rep = art.meta["compile_report"]
    assert not [r for r in rep["families"] if "skipped" in r]
    q8_rows = [r for r in rep["families"] if r.get("dtype") == "int8"]
    assert len(q8_rows) == 1 and "latency_ms" in q8_rows[0]
    assert "quant_mean_abs_err" in q8_rows[0]


def test_compile_model_grid_has_row_for_every_cell():
    # Every (family, dtype) cell must appear in the report exactly once —
    # measured, pruned_by_cost, or typed skip — never a silent hole.
    m = _svm(21, d=8, n_sv=40, heads=3)
    art = compile_model(
        m, Budget(max_err=10.0), seed=3,
        family_opts={"fourier": {"structured": True, "num_features": 32}},
    )
    rows = [
        (r["family"], r.get("dtype"))
        for r in art.meta["compile_report"]["families"]
    ]
    expected = [(f, dt) for f in FAMILIES for dt in ("float32", "int8")]
    assert sorted(rows) == sorted(expected)


def test_fourier_structured_int8_compiles_and_serves():
    art = get_family("fourier").compile(
        _svm(20), structured=True, dtype="int8", num_features=32
    )
    assert art.dtype == "int8"
    assert art.meta["projection"] == "fastfood"
    assert "quant_mean_abs_err" in art.meta
    assert art.arrays["ff_g"].dtype == jnp.int8
    assert art.arrays["weights"].dtype == jnp.int8
