"""The common tiled-kernel layer: TileConfig resolution, the tuning
registry, K-axis head-block tiling of the fused quadform kernel (tiled ==
untiled bit-for-bit; VMEM-budgeted block_k), backend dispatch via
$REPRO_SVM_BACKEND, and Pallas-interpret vs XLA path agreement."""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import backend
from repro.core.maclaurin import ApproxModel
from repro.kernels.common import TileConfig, autotune, tiles, tuning
from repro.kernels.quadform.kernel import quadform_heads_pallas
from repro.kernels.quadform.ref import quadform_heads_ref
from repro.kernels.rbf_pred.kernel import rbf_predict_pallas
from repro.serve.svm_engine import SVMEngine


@pytest.fixture(autouse=True)
def _clean_tuning():
    tuning.clear_overrides()
    yield
    tuning.clear_overrides()


def _random_heads(K, d, seed=0, gamma=0.05):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((K, d, d)).astype(np.float32) * 0.1
    M_all = jnp.asarray((M + M.transpose(0, 2, 1)) / 2)
    V = jnp.asarray(rng.standard_normal((K, d)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal(K).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(K).astype(np.float32))
    g = jnp.full((K,), gamma, jnp.float32)
    msq = jnp.full((K,), 2.0, jnp.float32)
    return M_all, V, c, b, g, msq


# ------------------------------------------------------------ tiles/config


def test_tile_arithmetic():
    assert tiles.round_up(1, 128) == 128
    assert tiles.round_up(128, 128) == 128
    assert tiles.round_up(129, 128) == 256
    assert tiles.lane_pad(3) == 128
    assert tiles.lane_pad(784) == 896
    assert tiles.grid_blocks(513, 64) == 9
    x = tiles.pad_tail(jnp.ones((3, 5)), 8, 128)
    assert x.shape == (8, 128) and float(x.sum()) == 15.0


def test_tileconfig_block_k_budget():
    """block_k auto-resolution keeps the whole double-buffered grid step
    (Hessian block, Z tile, per-head rows, outputs, temporaries) under the
    scoped-VMEM budget, floored at one head, evened out over its blocks."""
    d_pad = 896                                  # mnist d=784 lane-padded
    cfg = TileConfig(block_n=256, vmem_limit_mb=32)
    bk = cfg.resolve_block_k(10, d_pad)
    assert cfg.quadform_vmem_bytes(bk, d_pad) <= 32 << 20
    assert cfg.quadform_vmem_bytes(bk + 1, d_pad) > 32 << 20   # largest that fits
    assert 2 * bk * d_pad * d_pad * 4 <= 32 << 20   # the double buffer counts
    # int8 Hessian blocks are a quarter the size: more heads per block
    assert cfg.resolve_block_k(10, d_pad, m_itemsize=1, per_head_rows=2) > bk
    # 9 heads fit but 10 do not: two blocks of 5, not 9 + 1 padded to 18
    cfg9 = TileConfig(block_n=8, vmem_limit_mb=58)
    assert cfg9.quadform_vmem_bytes(10, d_pad) > 58 << 20 >= cfg9.quadform_vmem_bytes(9, d_pad)
    assert cfg9.resolve_block_k(10, d_pad) == 5
    # one head over budget still runs (smallest possible tile)
    assert TileConfig(vmem_limit_mb=1).resolve_block_k(10, 2048) == 1
    # explicit block_k wins, capped at K
    assert TileConfig(block_k=4).resolve_block_k(10, d_pad) == 4
    assert TileConfig(block_k=64).resolve_block_k(10, d_pad) == 10


def test_tileconfig_is_jit_static():
    cfg = TileConfig(block_n=64)
    assert hash(cfg) == hash(TileConfig(block_n=64))

    @jax.jit
    def f(x, cfg: TileConfig = cfg):
        return x

    calls = jax.jit(lambda x, c: x * c.block_n, static_argnums=1)
    assert float(calls(jnp.float32(2.0), cfg)) == 128.0


# ---------------------------------------------------------- tuning registry


def test_bucket_policy_shared_with_engine():
    """Dispatch-level lookups key on the SAME buckets the engine pads to
    and the sweep records — a batch of 1000 resolves the 1024 entry."""
    from repro.serve.svm_engine import bucket_size

    assert tuning.bucket(1000) == 1024
    assert tuning.bucket(5) == 32
    assert tuning.bucket(9000) == 8192
    for n in (1, 32, 33, 100, 1000, 8192, 10_000):
        assert tuning.bucket(n) == bucket_size(n)
    tuned = TileConfig(block_n=128)
    tuning.record("quadform", tuning.shape_key(d=64, k=1, n=1024), tuned)
    key_for_1000 = tuning.shape_key(d=64, k=1, n=tuning.bucket(1000))
    assert tuning.lookup("quadform", key_for_1000) == tuned


def test_tuning_lookup_default_and_override():
    key = tuning.shape_key(d=64, k=10, n=1024)
    assert key == "d64_k10_n1024"
    assert tuning.lookup("quadform", key) == tuning.DEFAULTS["quadform"]
    with pytest.raises(KeyError):
        tuning.lookup("quadform", key, strict=True)
    tuned = TileConfig(block_n=128)
    tuning.record("quadform", key, tuned, measured_ms=1.0, default_ms=2.0)
    assert tuning.lookup("quadform", key) == tuned
    assert tuning.lookup("quadform", key, strict=True) == tuned
    # other buckets unaffected
    assert tuning.lookup("quadform", "d64_k10_n32") == tuning.DEFAULTS["quadform"]
    with pytest.raises(KeyError):
        tuning.lookup("nonexistent_kernel")


def test_tuning_table_roundtrip(tmp_path):
    path = str(tmp_path / "table.json")
    tuned = TileConfig(block_n=64, block_m=128)
    tuning.lookup("quadform", "warm_the_default_table_cache")
    tuning.record("rbf_pred", "d100_m512_n256", tuned, measured_ms=0.5,
                  source="unit-test")
    tuning.save_table(path)
    with open(path) as f:
        saved = json.load(f)
    entry = saved["entries"][tuning.platform()]["rbf_pred"]["d100_m512_n256"]
    assert entry["config"]["block_n"] == 64
    assert entry["measured_ms"] == 0.5
    assert TileConfig.from_json(entry["config"]) == tuned
    # saving to a scratch path must not dump the checked-in default table
    # into it, nor leak the override into the cached default table
    assert set(saved["entries"][tuning.platform()]) == {"rbf_pred"}
    tuning.clear_overrides()
    assert tuning.lookup("rbf_pred", "d100_m512_n256") == tuning.DEFAULTS["rbf_pred"]


def test_load_table_validates_and_roundtrips(tmp_path):
    """save_table -> load_table round-trips clean entries; malformed keys,
    unknown kernels and bad configs are dropped with a warning instead of
    surfacing later as a KeyError mid-trace."""
    path = str(tmp_path / "table.json")
    tuned = TileConfig(block_n=64)
    tuning.record("quadform", "d64_k1_n256", tuned, measured_ms=0.25,
                  platform_name="cpu")
    tuning.save_table(path)
    table = tuning.load_table(path)                       # clean: no warning
    entry = table["entries"]["cpu"]["quadform"]["d64_k1_n256"]
    assert TileConfig.from_json(entry["config"]) == tuned

    # corrupt the file with every malformation class
    table["entries"]["cpu"]["not_a_kernel"] = {"d64_n32": {"config": {"block_n": 8}}}
    table["entries"]["cpu"]["rbf_pred"] = {
        "TOTALLY wrong key!": {"config": {"block_n": 8}},     # bad key
        "d64_m512_n256": {"config": {"block_n": -5}},         # bad config value
        "d32_m512_n256": {"note": "no config at all"},        # missing config
        "d16_m512_n256": {"config": {"block_n": 128}},        # survivor
    }
    with open(path, "w") as f:
        json.dump(table, f)
    with pytest.warns(UserWarning) as warned:
        clean = tuning.load_table(path)
    assert len(warned) == 4
    assert "not_a_kernel" not in clean["entries"]["cpu"]
    assert set(clean["entries"]["cpu"]["rbf_pred"]) == {"d16_m512_n256"}
    # the pre-existing good entry survives validation untouched
    assert clean["entries"]["cpu"]["quadform"]["d64_k1_n256"] == entry


def test_load_table_rejects_malformed_top_level(tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        json.dump({"entries": ["this", "is", "not", "a", "dict"]}, f)
    with pytest.warns(UserWarning, match="top-level structure"):
        assert tuning.load_table(path) == {"version": 1, "entries": {}}
    with open(path, "w") as f:
        f.write("{ not json")
    assert tuning.load_table(path) == {"version": 1, "entries": {}}


def test_autotune_picks_fastest_and_records():
    key = "unit_test_key"
    seen = []

    def build(cfg):
        def run():
            seen.append(cfg)
            return jnp.zeros(())
        return run

    winner, rows = autotune.autotune(
        "quadform", key, build,
        [TileConfig(block_n=64), TileConfig(block_n=256)],
        repeats=1, warmup=0,
    )
    # the default was appended: 3 candidates timed, winner recorded
    assert len(rows) == 3
    assert any(r["config"] == tuning.DEFAULTS["quadform"] for r in rows)
    assert tuning.lookup("quadform", key, strict=True) == winner
    assert winner == min(rows, key=lambda r: r["ms"])["config"]


# ------------------------------------------------------- backend dispatch


def test_env_var_backend_override(monkeypatch):
    monkeypatch.setattr(backend, "_forced", None)
    monkeypatch.setenv("REPRO_SVM_BACKEND", "pallas")
    assert backend.resolve() == "pallas"
    monkeypatch.setenv("REPRO_SVM_BACKEND", "xla")
    assert backend.resolve() == "xla"
    monkeypatch.setenv("REPRO_SVM_BACKEND", "auto")
    assert backend.resolve() == ("pallas" if jax.default_backend() == "tpu" else "xla")
    monkeypatch.setenv("REPRO_SVM_BACKEND", "cuda")
    with pytest.raises(ValueError):
        backend.resolve()
    # set_backend beats the env var
    monkeypatch.setenv("REPRO_SVM_BACKEND", "xla")
    prev = backend.set_backend("pallas")
    try:
        assert backend.resolve() == "pallas"
    finally:
        backend.set_backend(prev or "auto")


@pytest.mark.parametrize("K", [1, 4])
def test_quadform_pallas_vs_xla_paths_agree(K):
    """The two dispatch targets are the same math: Pallas (interpret) and
    the stacked-Hessian XLA GEMM agree to fp tolerance."""
    n, d = 97, 50
    rng = np.random.default_rng(K)
    Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32) * 0.5)
    heads = _random_heads(K, d, seed=K + 10)
    s_p, zsq_p, v_p = quadform_heads_pallas(
        Z, *heads, config=TileConfig(block_n=32), interpret=True
    )
    s_x, zsq_x, v_x = backend.quadform_heads_xla(Z, *heads)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_x), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(zsq_p), np.asarray(zsq_x), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(v_p), np.asarray(v_x))


def test_rbf_pred_pallas_vs_xla_paths_agree():
    n, m, d = 130, 300, 37
    rng = np.random.default_rng(7)
    Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
    a = jnp.asarray(rng.standard_normal(m).astype(np.float32))
    f_p = rbf_predict_pallas(
        Z, X, a, 0.07, 0.3, config=TileConfig(block_n=64, block_m=128), interpret=True
    )
    f_x = backend.rbf_scores_xla(Z, X, a, 0.07, 0.3)
    np.testing.assert_allclose(np.asarray(f_p), np.asarray(f_x), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("K", [3, 10])
def test_rbf_pred_heads_share_one_sv_pass(K):
    """(K, m) alpha_y rows score every head in one pass: each column
    equals that head's single-head call, with per-head biases."""
    n, m, d = 70, 300, 37
    rng = np.random.default_rng(K)
    Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
    a = jnp.asarray(rng.standard_normal((K, m)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(K).astype(np.float32))
    cfg = TileConfig(block_n=32, block_m=128)
    f_p = rbf_predict_pallas(Z, X, a, 0.07, b, config=cfg, interpret=True)
    assert f_p.shape == (n, K)
    np.testing.assert_allclose(
        np.asarray(f_p), np.asarray(backend.rbf_scores_xla(Z, X, a, 0.07, b)),
        rtol=2e-5, atol=2e-5,
    )
    for k in range(K):
        one = rbf_predict_pallas(Z, X, a[k], 0.07, b[k], config=cfg, interpret=True)
        np.testing.assert_allclose(np.asarray(f_p[:, k]), np.asarray(one),
                                   rtol=1e-6, atol=1e-6)


def test_backend_dispatch_routes_to_pallas(monkeypatch):
    """Forcing pallas off-TPU runs the kernels in interpret mode through
    the SAME dispatch entry points the engine uses."""
    prev = backend.set_backend("pallas")
    try:
        n, d, K = 40, 12, 3
        rng = np.random.default_rng(0)
        Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32) * 0.4)
        heads = _random_heads(K, d, seed=3)
        s, _, _ = backend.quadform_heads(Z, *heads)
        s_ref, _, _ = quadform_heads_ref(Z, *heads)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-5, atol=1e-5)
    finally:
        backend.set_backend(prev or "auto")


# ------------------------------------------------ K-axis head-block tiling


def test_k_tiled_matches_untiled_bit_for_bit():
    """Head-blocks are independent: the tiled kernel's fp32 scores are
    IDENTICAL to the fully-resident kernel's, not merely close."""
    n, d, K = 65, 30, 10
    rng = np.random.default_rng(42)
    Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32) * 0.5)
    heads = _random_heads(K, d, seed=5)
    untiled = quadform_heads_pallas(
        Z, *heads, config=TileConfig(block_n=32, block_k=K), interpret=True
    )
    for block_k in (1, 2, 3, 4):                 # 3 exercises K % block_k != 0
        tiled = quadform_heads_pallas(
            Z, *heads, config=TileConfig(block_n=32, block_k=block_k), interpret=True
        )
        for t, u in zip(tiled, untiled):
            np.testing.assert_array_equal(np.asarray(t), np.asarray(u))


def test_k_tiled_mnist_shape_under_vmem_budget():
    """The acceptance shape: K=10 heads at d=784 (mnist OvR). The stacked
    Hessian is ~31 MB f32 — over a single core's VMEM — but every grid
    step's slice stays under the configured budget, and the scores match
    the untiled kernel bit-for-bit and the vmap oracle to tolerance."""
    n, d, K = 48, 784, 10
    budget_mb = 8
    d_pad = tiles.lane_pad(d)
    cfg = TileConfig(block_n=48, vmem_limit_mb=budget_mb)
    block_k = cfg.resolve_block_k(K, d_pad)
    assert K * d_pad * d_pad * 4 > 16 << 20      # full stack busts VMEM...
    assert block_k * d_pad * d_pad * 4 <= budget_mb << 20   # ...each slice fits
    assert 1 <= block_k < K

    rng = np.random.default_rng(0)
    Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32) * 0.1)
    heads = _random_heads(K, d, seed=1, gamma=1e-3)
    tiled = quadform_heads_pallas(Z, *heads, config=cfg, interpret=True)
    untiled = quadform_heads_pallas(
        Z, *heads, config=TileConfig(block_n=48, block_k=K), interpret=True
    )
    for t, u in zip(tiled, untiled):
        np.testing.assert_array_equal(np.asarray(t), np.asarray(u))
    s_ref, _, _ = quadform_heads_ref(Z, *heads)
    np.testing.assert_allclose(
        np.asarray(tiled[0]), np.asarray(s_ref), rtol=1e-4, atol=1e-4
    )


# ------------------------------------------------- engine bucket resolution


def _toy_engine(**kw):
    d = 6
    rng = np.random.default_rng(0)
    M = rng.standard_normal((d, d)).astype(np.float32) * 0.1
    am = ApproxModel(
        c=jnp.float32(0.3),
        v=jnp.asarray(rng.standard_normal(d).astype(np.float32)),
        M=jnp.asarray((M + M.T) / 2),
        b=jnp.float32(-0.1),
        gamma=jnp.float32(0.05),
        max_sv_sq_norm=jnp.float32(2.0),
    )
    return SVMEngine(am, None, **kw)


def test_engine_resolves_tuned_config_per_bucket():
    tuned = TileConfig(block_n=16)
    tuning.record("quadform", tuning.shape_key(d=6, k=1, n=32), tuned)
    eng = _toy_engine(min_bucket=32, max_batch=64)
    eng.warmup()
    # bucket 32 picked up the measured entry (clamped block_n intact),
    # bucket 64 fell back to the default (clamped to the bucket)
    assert eng.bucket_configs[32].block_n == 16
    assert eng.bucket_configs[64].block_n == min(
        tuning.DEFAULTS["quadform"].block_n, 64
    )
    f, _ = eng.predict(np.zeros((5, 6), np.float32))
    assert f.shape == (5,)


def test_engine_explicit_tile_config_pins_all_buckets():
    eng = _toy_engine(min_bucket=32, max_batch=64, tile_config=TileConfig(block_n=8))
    eng.warmup()
    assert all(c.block_n == 8 for c in eng.bucket_configs.values())
