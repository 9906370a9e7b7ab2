"""Ahead-of-time compiles of the solve path's Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: a kernel
lowered against a *described* ``v5e:2x2`` topology is refused here for what
interpret mode cannot see (block tiling, i64 index maps, non-32-bit SMEM
scalars, SMEM capacity).  Shapes are those ``chip_smoke.py`` runs; every
operand is float32 while the suite keeps x64 on, so the int32 index-map
discipline stays guarded.  Nothing runs, so nothing here is a timing.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist each
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sparse import BellMeta
from repro.kernels import solve_step as fk
from repro.kernels import supernode as ksn
from repro.kernels.spmv_bell import bell_spmv_pallas
from repro.kernels.stencil5 import Stencil5Meta, stencil5_pallas
from test_kernels import _FUSED_SIGS as FUSED_SIGS

F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(*dims, dtype=F32)`` → an abstract operand on one v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *dims, dtype=F32: jax.ShapeDtypeStruct(dims, dtype,
                                                         sharding=one_chip)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


N_STENCIL = 4096 * 4096          # chip_smoke's stencil phase


@pytest.mark.parametrize("name", sorted(FUSED_SIGS))
def test_fused_step_compiles(shape, name):
    n_vec, n_sc = FUSED_SIGS[name]
    kern = getattr(fk, name)
    _compile(lambda *a: kern(*a, interpret=False),
             *([shape(N_STENCIL)] * n_vec + [shape()] * n_sc))


def test_fused_step_compiles_vmapped(shape):
    """The served path vmaps the fused steps over a request batch."""
    step = jax.vmap(lambda x, r, p, s, a: fk.fused_cg_halfstep(
        x, r, p, s, a, interpret=False))
    _compile(step, *([shape(16, 4096)] * 4 + [shape(16)]))


def test_stencil5_compiles(shape):
    meta = Stencil5Meta(nx=4096, ny=4096)
    _compile(lambda v, x: stencil5_pallas(meta, v, x, False),
             shape(5, 4096, 4096), shape(4096, 4096))


@pytest.mark.parametrize("n_rb,k,batch", [
    (32768, 69, None),     # graph phase: 2^18-node geometric graph
    (512, 2, 16),          # serve phase: 64^2 Poisson, vmapped requests
])
def test_bell_spmv_compiles(shape, n_rb, k, batch):
    bm, bn = 8, 128
    n = n_rb * bm
    meta = BellMeta(bm=bm, bn=bn, n_rb=n_rb, n_cb=n // bn, k=k, n_pad=n,
                    m_pad=n, fill=0.0)
    fn = lambda c, v, x: bell_spmv_pallas(meta, c, v, x, False)
    lead = () if batch is None else (batch,)
    if batch is not None:
        fn = jax.vmap(fn, in_axes=(None, 0, 0))
    _compile(fn, shape(n_rb, k, dtype=I32), shape(*lead, n_rb, k, bm, bn),
             shape(*lead, n))


# (lanes, wb, rb): the narrowest bucket and the widest one of 2-D Poisson at
# 1e5 DOF (chip_smoke's direct phase)
BUCKETS = [(64, 2, 4), (8, 32, 1024)]


@pytest.mark.parametrize("k,wb,rb", BUCKETS)
@pytest.mark.parametrize("pairs", [False, True])
def test_panel_factor_compiles(shape, k, wb, rb, pairs):
    _compile(lambda P, Q, w, r, t, m: ksn.panel_factor(
        P, Q, w, r, t, m, pairs=pairs, interpret=False),
        shape(k, wb + rb, wb), shape(k, wb, rb), shape(k, dtype=I32),
        shape(k, dtype=I32), shape(), shape(k, wb, dtype=jnp.bool_))


@pytest.mark.parametrize("k,wb,rb", BUCKETS)
def test_schur_update_compiles(shape, k, wb, rb):
    _compile(lambda P, Q: ksn.schur_update(P, Q, interpret=False),
             shape(k, wb + rb, wb), shape(k, wb, rb))


@pytest.mark.parametrize("k,wb,rb", BUCKETS)
@pytest.mark.parametrize("mode", ["l", "lt", "u", "ut"])
def test_block_trsv_compiles(shape, k, wb, rb, mode):
    _compile(lambda D, y, w, m: ksn.block_trsv(
        D, y, w, m, mode=mode, pairs=True, interpret=False),
        shape(k, wb, wb), shape(k, wb), shape(k, dtype=I32),
        shape(k, wb, dtype=jnp.bool_))
