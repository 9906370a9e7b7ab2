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
from repro.kernels.stencil27 import (Stencil27Meta, stencil27_pallas,
                                     symgs27_pallas)
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


def _kernel_calls(text):
    import re
    return re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_"
                      r"call\"", text)


# the HPCG cell's finest (256^3) and coarsest (32^3) grids
@pytest.mark.parametrize("ng", [256, 32])
def test_stencil27_compiles(shape, ng):
    meta = Stencil27Meta(ng, ng, ng)
    text = _compile(lambda P, x: stencil27_pallas(meta, P, x, "stencil27_l0",
                                                  False),
                    shape(27, ng, ng, ng), shape(ng, ng, ng))
    assert all(c.startswith("stencil27_l0.") for c in _kernel_calls(text))


@pytest.mark.parametrize("ng", [256, 32])
def test_symgs27_compiles(shape, ng):
    meta = Stencil27Meta(ng, ng, ng)
    text = _compile(lambda P, x, b: symgs27_pallas(meta, P, x, b,
                                                   "symgs27_l0", False),
                    shape(27, ng, ng, ng), shape(ng, ng, ng),
                    shape(ng, ng, ng))
    calls = _kernel_calls(text)
    assert len(calls) == 4 and all(c.startswith("symgs27_l0.") for c in calls)


# the MG cells' finest transfers: the 2-D full weighting at 4096^2 and the
# HPCG injection at 256^3, each fed r - A x as in the V-cycle.  A form that
# strides the lane axis moves 8.9 and 9.9 GB a call (the 2-D one through a
# 4.3 GB padded temporary); the lane-dense forms move 0.6 and 0.4 GB.
@pytest.mark.parametrize("restrict,dims", [
    ("_restrict", (4096, 4096)),
    ("_inject", (256, 256, 256)),
])
def test_mg_restriction_keeps_lanes_dense(shape, restrict, dims):
    from repro.core import multigrid
    fn = getattr(multigrid, restrict)
    compiled = jax.jit(lambda b, y: fn(b - y)).lower(
        shape(*dims), shape(*dims)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 1e9, cost["bytes accessed"]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 100e6, temp
    # the lane compaction is a dot at full f32 precision, not one bf16 pass
    assert "operand_precision={highest,highest}" in compiled.as_text()


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


def test_loop_kernels_are_named_after_themselves(shape, monkeypatch):
    """Inside the fused MG-style CG loop (a ``while`` body under the
    ``krylov.cg_fused`` scope) the half-step kernel's custom call is named
    ``fused_cg_halfstep.N``, not after the loop body, and the stencil
    kernel's stays ``stencil5_pallas.N`` under the ``spmv.stencil`` scope."""
    import re

    from repro.core.solvers import cg_fused
    from repro.kernels import ops
    from repro.kernels.ops import stencil5_matvec
    monkeypatch.setattr(ops, "_interpret", lambda: False)  # compile for v5e
    ng = 256
    meta = Stencil5Meta(nx=ng, ny=ng)

    def solve(val, b):
        x, _ = cg_fused(lambda v: stencil5_matvec(meta, val, v), b,
                        M=lambda r: 0.25 * r, maxiter=3, interpret=False)
        return x

    text = _compile(solve, shape(5 * ng * ng), shape(ng * ng))
    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_"
                       r"call\"", text)
    assert any(re.fullmatch(r"fused_cg_halfstep\.\d+", c) for c in calls), calls
    assert any(re.fullmatch(r"stencil5_pallas\.\d+", c) for c in calls), calls
    assert " while(" in text
    assert not any(c.startswith("body") for c in calls), calls


def test_plan_solve_program_compiles(shape, monkeypatch):
    """The plan engine's whole MG-CG solve program for a stencil plan — the
    V-cycle prologue, the fused loop and its kernels in one ``jax.jit`` —
    compiles for a v5e from its argument shapes, the plan's arrays among
    them, with the kernels keeping their names.  x64 off, as on the chip:
    under x64 the MG hierarchy's dense coarse operator is float64."""
    monkeypatch.setattr(fk, "default_interpret", lambda: False)
    with jax.enable_x64(False):
        _compile_plan_program(shape)


def _compile_plan_program(shape):
    import re

    import numpy as np

    from repro.core import get_plan, make_config
    from repro.data.poisson import poisson2d_vc
    ng = 256
    kappa = np.exp(0.3 * np.random.default_rng(0).normal(size=(ng, ng)))
    A = poisson2d_vc(jnp.asarray(kappa, F32), use_stencil_kernel=True)
    A = A.with_values(A.val.astype(F32))
    cfg = make_config(A, precond="mg")
    plan = get_plan(A, cfg)
    state = plan.setup(A)
    program, args = plan.backend.solve_program(
        plan, state, jnp.ones(ng * ng, F32), None, cfg)
    abstract = jax.tree_util.tree_map(
        lambda a: shape(*a.shape, dtype=a.dtype)
        if isinstance(a, jax.Array) else a, args)
    text = program.lower(*abstract).compile().as_text()
    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_"
                       r"call\"", text)
    assert any(re.fullmatch(r"fused_cg_halfstep\.\d+", c) for c in calls), calls
    assert any(re.fullmatch(r"stencil5_pallas\.\d+", c) for c in calls), calls
    assert " while(" in text


def test_hpcg_plan_solve_program_compiles(shape, monkeypatch):
    """The 3-D MG-CG solve program of an HPCG plan (64^3, four levels) with
    the fused CG steps compiles for a v5e, every level's SpMV and SymGS
    launches named after their level."""
    import re

    from repro import sla
    from repro.core import get_plan, make_config
    from repro.data.hpcg import hpcg_problem
    monkeypatch.setattr(fk, "default_interpret", lambda: False)
    with jax.enable_x64(False), sla.options(fused_step="on"):
        A = hpcg_problem(64, 64, 64)
        cfg = make_config(A, method="cg", precond="mg")
        plan = get_plan(A, cfg)
        state = plan.setup(A)
        program, args = plan.backend.solve_program(
            plan, state, jnp.ones(A.shape[0], F32), None, cfg)
        abstract = jax.tree_util.tree_map(
            lambda a: shape(*a.shape, dtype=a.dtype)
            if isinstance(a, jax.Array) else a, args)
        text = program.lower(*abstract).compile().as_text()
    names = {re.sub(r"\.\d+$", "", c) for c in _kernel_calls(text)}
    assert {f"symgs27_l{k}" for k in range(4)} <= names, names
    assert {f"stencil27_l{k}" for k in range(3)} <= names, names
    assert "fused_cg_halfstep" in names
