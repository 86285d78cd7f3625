"""Distribution tests: adaptive-parallelism rules + 8-device subprocess
dry-runs (XLA device-count flag must be set before jax import, hence
subprocess)."""
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_adaptive_parallelism_rules_differ_by_phase():
    """FIXAR §V-B: inference emphasizes intra-layer (model-axis) splits,
    training emphasizes intra-batch (data-axis) splits."""
    import jax
    from repro.core.parallelism import serve_rules, train_rules
    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((1, 1), ("data", "model"))
    tr = train_rules(mesh)
    sv_long = serve_rules(mesh, shard_kv_seq=True)
    assert tr.rules["batch"] == "data"          # intra-batch for training
    assert tr.rules["mlp"] == "model"
    assert sv_long.rules["batch"] is None       # single request: batch idle
    assert sv_long.rules["kv_seq"] == "data"    # sequence-parallel decode
    assert sv_long.rules["mlp"] == "model"      # intra-layer split


def test_divisibility_guard_drops_axis():
    import jax
    from repro.core.parallelism import train_rules
    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    rules = train_rules(mesh)
    spec = rules.mesh_axes(("kv_heads",), shape=(1,), mesh=FakeMesh())
    assert spec == jax.sharding.PartitionSpec(None)
    spec2 = rules.mesh_axes(("kv_heads",), shape=(32,), mesh=FakeMesh())
    assert spec2 == jax.sharding.PartitionSpec("model")


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, r"{src}")
import jax
from repro.configs import registry
from repro.launch.dryrun import build_cell
from repro.launch.mesh import make_debug_mesh
from repro.models.config import ShapeConfig

arch, kind = sys.argv[1], sys.argv[2]
cfg = registry.get_smoke(arch)
shape = {{"train": ShapeConfig("t", "train", 256, 8),
          "prefill": ShapeConfig("p", "prefill", 512, 4),
          "decode": ShapeConfig("d", "decode", 512, 8)}}[kind]
mesh = make_debug_mesh(multi_pod=(sys.argv[3] == "multi"))
with jax.set_mesh(mesh):
    jitted, args = build_cell(cfg, shape, mesh, qat=True)
    compiled = jitted.lower(*args).compile()
    print("COMPILED", compiled.cost_analysis().get("flops", 0.0))
"""


def _run_subproc(arch, kind, pod="single"):
    script = _SUBPROC.format(src=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", script, arch, kind, pod],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COMPILED" in out.stdout


@pytest.mark.parametrize("arch,kind", [
    ("qwen2_0_5b", "train"),
    ("dbrx_132b", "train"),        # MoE: EP dispatch collectives
    ("rwkv6_1_6b", "decode"),      # recurrent state decode
    ("gemma3_1b", "prefill"),      # local:global mix
])
def test_debug_mesh_cell_compiles(arch, kind):
    _run_subproc(arch, kind)


def test_multi_pod_axis_shards():
    _run_subproc("qwen2_0_5b", "train", "multi")
