"""Compile every main-path Pallas kernel for a described TPU v5e.

Interpret mode (what the rest of the suite runs) accepts layouts, slices
and VMEM budgets that Mosaic refuses; these tests hand the kernels to the
TPU compiler at the serving widths of the ``mnist`` deployment (d=780,
K=10, 8,192 SVs) and of the head-sharded K=4096 model (d=32, K=1024 per
chip), and assert a Mosaic kernel (``tpu_custom_call``) is in the
executable. Nothing runs: a compile that passes is not a chip run.

The topology is described only inside the fixture below, never while a
module is imported: one process at a time may load the TPU library, and
every test worker imports every test file.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import backend
from repro.kernels.common import tuning
from repro.kernels.fwht.kernel import fastfood_score_pallas, fastfood_score_q8_pallas
from repro.kernels.quadform.kernel import quadform_heads_pallas, quadform_heads_q8_pallas
from repro.kernels.rbf_pred.kernel import rbf_predict_pallas
from repro.kernels.rff_score.kernel import rff_score_pallas, rff_score_q8_pallas

F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # described-device executables can be written to the persistent cache
    # but not read back without a chip: keep this file off it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure to describe: skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _cfg(kernel):
    return tuning.lookup(kernel, platform_name="tpu")


@pytest.mark.parametrize("d,k", [(780, 10), (32, 1024)])
def test_quadform_compiles(one_chip, d, k):
    _compile(
        lambda *a: quadform_heads_pallas(*a, config=_cfg("quadform")),
        [((256, d), F32), ((k, d, d), F32), ((k, d), F32)] + [((k,), F32)] * 4,
        one_chip,
    )


@pytest.mark.parametrize("d,k", [(780, 10), (32, 1024)])
def test_quadform_q8_compiles(one_chip, d, k):
    _compile(
        lambda *a: quadform_heads_q8_pallas(*a, config=_cfg("quadform_q8")),
        [((256, d), F32), ((k, d, d), I8), ((k, d), F32), ((k, d), F32)]
        + [((k,), F32)] * 4,
        one_chip,
    )


@pytest.mark.parametrize("n,d,k,m", [(256, 780, 10, 8192), (5, 780, 10, 8192),
                                     (8192, 2000, 1, 36988)], ids=["256", "5", "epsilon"])
def test_rbf_pred_compiles(one_chip, n, d, k, m):
    # a full bucket (breaker-degraded serving) and a handful of per-row
    # fallback rows, all ten heads in one pass over 8,192 SVs; and
    # epsilon's full bucket at d = 2,000 over its 36,988 SVs, whose
    # tiles need more than the default scoped VMEM
    _compile(
        lambda Z, X, a, g, b: rbf_predict_pallas(Z, X, a, g, b, config=_cfg("rbf_pred")),
        [((n, d), F32), ((m, d), F32), ((k, m), F32), ((), F32), ((k,), F32)],
        one_chip,
    )


def test_rff_score_compiles(one_chip):
    _compile(
        lambda *a: rff_score_pallas(*a, config=_cfg("rff_score")),
        [((256, 780), F32), ((2048, 780), F32), ((2048,), F32), ((10, 2048), F32),
         ((10,), F32)],
        one_chip,
    )


def test_rff_score_q8_compiles(one_chip):
    _compile(
        lambda *a: rff_score_q8_pallas(*a, config=_cfg("rff_score_q8")),
        [((256, 780), F32), ((2048, 780), I8), ((2048,), F32), ((2048,), F32),
         ((10, 2048), I8), ((10,), F32), ((10,), F32)],
        one_chip,
    )


def test_fwht_compiles(one_chip):
    # d=780 -> d'=1024, F=2048: two Fastfood stacks
    _compile(
        lambda *a: fastfood_score_pallas(*a, config=_cfg("fwht")),
        [((256, 780), F32), ((2, 1024), F32), ((2, 1024), F32), ((2, 1024), I32),
         ((2, 1024), F32), ((2048,), F32), ((10, 2048), F32), ((10,), F32)],
        one_chip,
    )


def test_fwht_q8_compiles(one_chip):
    _compile(
        lambda *a: fastfood_score_q8_pallas(*a, config=_cfg("fwht_q8")),
        [((256, 780), F32), ((2, 1024), I8), ((2, 1024), I8), ((2, 1024), I32),
         ((2, 1024), I8), ((2,), F32), ((2048,), F32), ((10, 2048), I8),
         ((10,), F32), ((10,), F32)],
        one_chip,
    )


def test_head_sharded_quadform_compiles_on_four_chips(topo, monkeypatch):
    # the K=4096, d=32 OvR model with its heads split over the 2x2 mesh:
    # each chip runs the Pallas kernel on its 1,024 heads
    monkeypatch.setattr(backend, "_forced", "pallas")
    monkeypatch.setattr(backend, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("heads",))
    k, d = 4096, 32

    def spec(shape, dt, *axes):
        return jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, P(*axes)))

    args = [spec((256, d), F32), spec((k, d, d), F32, "heads"),
            spec((k, d), F32, "heads")] + [spec((k,), F32, "heads")] * 4
    fn = lambda *a: backend.quadform_heads_sharded(  # noqa: E731
        *a, mesh=mesh, config=_cfg("quadform")
    )
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
