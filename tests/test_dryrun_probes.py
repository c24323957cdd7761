"""Dry-run machinery tests that DON'T need 512 devices: the secant cost
extrapolation is validated against a full unroll on a 1×1 mesh, and the
collective-bytes HLO parser against hand-built collectives.

The full 40-cell × 2-mesh dry-run runs via
``python -m repro.launch.dryrun --all --both-meshes`` (EXPERIMENTS.md §Dry-run);
a single reduced-scale multi-device cell is exercised here in a subprocess
(so the forced device count cannot leak into this process's jax)."""
import os
import subprocess
import sys

import jax
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_secant_matches_full_unroll():
    """cost(L) extrapolated from L∈{1,2} == measured full unroll at L=4
    (whisper-tiny decoder is cost-linear in depth)."""
    from repro.configs.base import get_config
    from repro.launch.dryrun import _reconstruct, _with_layers, lower_cell
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.shapes import ShapeSpec

    cfg = get_config("whisper-tiny")
    import dataclasses
    cfg = dataclasses.replace(cfg.reduced(), enc_layers=1, enc_frames=16)
    shape = ShapeSpec("tiny_train", "train", 64, 4)
    mesh = make_debug_mesh(1, 1)

    costs = {}
    for L in (1, 2, 4):
        pcfg = _with_layers(cfg, L)
        lowered = lower_cell(pcfg, shape, mesh, unroll=L, q_chunk=0)
        costs[L] = float(lowered.compile().cost_analysis()
                         .get("flops", 0.0))
    want = costs[4]
    got = _reconstruct(dataclasses.replace(cfg, n_layers=4),
                       {1: costs[1], 2: costs[2]})
    assert abs(got - want) / want < 0.02, (got, want)


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %p0 = f32[2048]{0} parameter(0)
  %ag = f32[4096]{0} all-gather(f32[2048]{0} %p0), replica_groups={{0,1}}
  %ar = bf16[1024]{0} all-reduce(bf16[1024]{0} %x), to_apply=%sum
  %rs = f32[512]{0} reduce-scatter(f32[2048]{0} %p0), dimensions={0}
  %cp = f32[256]{0} collective-permute(f32[256]{0} %y)
"""
    out = collective_bytes(hlo)
    by = out["bytes_by_kind"]
    assert by["all-gather"] == 4096 * 4
    assert by["all-reduce"] == 2 * 1024 * 2
    assert by["reduce-scatter"] == 2048 * 4      # input bytes
    assert by["collective-permute"] == 256 * 4
    assert out["count_by_kind"]["all-gather"] == 1


def test_applicability_rules():
    from repro.configs.base import get_config
    from repro.launch.shapes import SHAPES, applicable
    long = SHAPES["long_500k"]
    for arch in ("qwen2.5-3b", "granite-20b", "yi-6b", "whisper-tiny",
                 "stablelm-12b", "olmoe-1b-7b", "phi-3-vision-4.2b"):
        ok, why = applicable(get_config(arch), long)
        assert not ok and "sub-quadratic" in why
    for arch in ("mamba2-2.7b", "recurrentgemma-9b", "mixtral-8x7b"):
        ok, _ = applicable(get_config(arch), long)
        assert ok
    for arch in ("qwen2.5-3b", "whisper-tiny"):
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            ok, _ = applicable(get_config(arch), SHAPES[s])
            assert ok


def test_input_specs_no_allocation():
    from repro.configs.base import all_configs
    from repro.launch.shapes import SHAPES, applicable, input_specs
    for name, cfg in all_configs().items():
        for sname, shape in SHAPES.items():
            if not applicable(cfg, shape)[0]:
                continue
            specs = input_specs(cfg, shape)
            for leaf in jax.tree.leaves(specs):
                assert isinstance(leaf, jax.ShapeDtypeStruct), (name, sname)


@pytest.mark.slow
def test_multidevice_cell_subprocess(tmp_path):
    """One reduced cell on a forced 8-device (2×4) mesh in a subprocess —
    proves the sharding rules hold on a real multi-device partitioning."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax
from repro.configs.base import get_config
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_debug_mesh
from repro.launch.shapes import ShapeSpec

mesh = make_debug_mesh(2, 4)
for arch in ("qwen2.5-3b", "mamba2-2.7b"):
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              d_model=256, n_heads=8, n_kv=4 if arch=="qwen2.5-3b" else 0,
                              head_dim=32, d_ff=512, vocab=1024)
    shape = ShapeSpec("t", "train", 128, 8)
    lowered = lower_cell(cfg, shape, mesh, unroll=1, q_chunk=0)
    c = lowered.compile()
    assert c.cost_analysis().get("flops", 0) > 0
    print(arch, "OK")
print("SUBPROCESS_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_OK" in out.stdout, out.stdout + out.stderr
