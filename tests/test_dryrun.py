"""Dry-run machinery tests: roofline HLO parsing units + an 8-device
subprocess mini dry-run (single- and multi-pod debug meshes)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.launch.roofline import (collective_bytes_structural,
                                   extrapolate_linear, model_flops_for,
                                   _shape_bytes)

HLO_SAMPLE = """\
HloModule jit_step, entry_computation_layout={()->()}

%region_0.10 (arg.11: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %ag.1 = f32[128,256]{1,0} all-gather(%p0), replica_groups={}, dimensions={0}
  %ar.1 = f32[64]{0} all-reduce(%p1), to_apply=%add
  ROOT %t = (s32[], f32[128,256]) tuple(%c, %ag.1)
}

%cond.20 (arg.21: (s32[], f32[128,256])) -> pred[] {
  %iter = s32[] get-tuple-element(%arg.21), index=0
  %bound = s32[] constant(22)
  ROOT %cmp = pred[] compare(%iter, %bound), direction=LT
}

ENTRY %main.30 (p: f32[16,16]) -> f32[16,16] {
  %w = (s32[], f32[128,256]) while(%init), condition=%cond.20, body=%region_0.10
  %rs = f32[32,8]{1,0} reduce-scatter(%x), dimensions={0}, to_apply=%add
  %cp-start = (f32[8,8], f32[8,8]) collective-permute-start(%y), source_target_pairs={{0,1}}
  %cp-done = f32[8,8] collective-permute-done(%cp-start)
  ROOT %r = f32[16,16] add(%p, %p)
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert _shape_bytes("bf16[8]") == 16
    assert _shape_bytes("(f32[4,4], s32[2])") == 64 + 8
    assert _shape_bytes("pred[10]") == 10


def test_collective_parse_with_trip_counts():
    by, counts, meta = collective_bytes_structural(HLO_SAMPLE)
    # while body collectives × trip 22
    assert by["all-gather"] == 128 * 256 * 4 * 22
    assert by["all-reduce"] == 64 * 4 * 22
    assert counts["all-gather"] == 22
    # entry collectives counted once
    assert by["reduce-scatter"] == 32 * 8 * 4
    # permute-start tuple halved (operand+result buffers), -done skipped
    assert by["collective-permute"] == 8 * 8 * 4
    assert meta["whiles"][0]["trip"] == 22


def test_extrapolate_linear():
    # cost(n) = 100 + 7n
    assert extrapolate_linear(1, 107, 2, 114, 10) == pytest.approx(170)
    assert extrapolate_linear(2, 114, 2, 114, 10) == 114  # degenerate


def test_model_flops_formulas():
    from repro.configs.base import SHAPES
    from repro.configs.registry import get_config

    dense = get_config("tinyllama-1.1b")
    moe = get_config("mixtral-8x7b")
    t = SHAPES["train_4k"]
    d = t.global_batch * t.seq_len
    assert model_flops_for(dense, t) == pytest.approx(
        6.0 * dense.param_count() * d)
    assert model_flops_for(moe, t) == pytest.approx(
        6.0 * moe.active_param_count() * d)
    dec = SHAPES["decode_32k"]
    assert model_flops_for(dense, dec) == pytest.approx(
        2.0 * dense.param_count() * dec.global_batch)


@pytest.mark.slow
@pytest.mark.parametrize("mesh", ["debug", "debug-multi"])
def test_mini_dryrun_subprocess(tmp_path, mesh):
    """Full dry-run path in a subprocess with 8 host devices: lower +
    compile + roofline for one small arch on single- and multi-pod debug
    meshes. This is the CI-sized version of the 512-chip run."""
    env = dict(os.environ, REPRO_DRYRUN_DEVICES="8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "xlstm-125m", "--shape", "train_4k", "--mesh", mesh,
         "--out-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    assert len(files) == 1
    rec = json.load(open(tmp_path / files[0]))
    assert rec["status"] == "ok"
    roof = rec["roofline"]
    assert roof["flops_per_chip"] > 0
    assert roof["bytes_per_chip"] > 0
    assert roof["dominant"] in ("compute", "memory", "collective")
    assert rec["memory_analysis"]["temp_size_in_bytes"] > 0


def test_cell_plans_build_for_every_arch_on_tiny_mesh():
    """make_cell_plan must produce coherent sharding trees for every arch ×
    shape (structure check only — no lowering here)."""
    import jax

    from repro.configs.base import SHAPES
    from repro.configs.registry import get_smoke_config, list_archs
    from repro.launch.steps import make_cell_plan

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        for arch in list_archs():
            cfg = get_smoke_config(arch)
            for shape_name, shape in SHAPES.items():
                if shape_name in cfg.skip_shapes:
                    continue
                import dataclasses

                small = dataclasses.replace(
                    shape, seq_len=32, global_batch=2)
                plan = make_cell_plan(cfg, small, mesh)
                assert plan.state_bytes > 0
                jax.tree_util.tree_structure(plan.in_shardings)
