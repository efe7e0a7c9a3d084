"""Sharding rules, program builder, and multi-device lowering (subprocess)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config
from repro.models import build_model
from repro.parallel.sharding import (
    LONG_RULES,
    SERVE_RULES,
    TRAIN_RULES,
    rules_for,
    spec_for,
    with_pod_axis,
)

REPO = Path(__file__).resolve().parents[1]


class _FakeMesh:
    def __init__(self, **shape):
        self.shape = shape


def test_spec_fallbacks_match_arch_realities():
    mesh = _FakeMesh(data=16, model=16)
    # granite: 32 heads shard over model; head_dim falls out
    s = spec_for((4096, 32, 128), ("fsdp", "heads", "head_dim"), TRAIN_RULES, mesh)
    assert tuple(s) == ("data", "model", None)
    # gemma2: 8 heads cannot shard 16-way; head_dim=256 claims model
    s = spec_for((2304, 8, 256), ("fsdp", "heads", "head_dim"), TRAIN_RULES, mesh)
    assert tuple(s) == ("data", None, "model")
    # mixtral MoE: 8 experts can't shard 16-way -> ff claims model (TP-MoE)
    s = spec_for((8, 4096, 14336), ("experts", "fsdp", "ff"), TRAIN_RULES, mesh)
    assert tuple(s) == (None, "data", "model")
    # phi3.5: 16 experts -> EP over model, ff unsharded
    s = spec_for((16, 4096, 6400), ("experts", "fsdp", "ff"), TRAIN_RULES, mesh)
    assert tuple(s) == ("model", "data", None)


def test_pod_axis_extends_batch():
    r = with_pod_axis(TRAIN_RULES)
    assert r["batch"] == ("pod", "data")
    assert r["heads"] == "model"


def test_rules_for_long_shards_weights_and_kv_seq():
    r = rules_for("long", multi_pod=False)
    assert r["kv_seq"] == "data" and r["fsdp"] == "data" and r["batch"] is None


def test_serve_rules_keep_batch_on_data():
    r = rules_for("decode", multi_pod=False)
    assert r["batch"] == "data" and r["fsdp"] is None


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mixtral-8x7b", "mamba2-2.7b"])
def test_param_axes_cover_every_leaf(arch):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    axes = model.param_axes()
    shapes = model.param_shapes(jnp.float32)
    flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    flat_s = jax.tree.leaves(shapes)
    assert len(flat_a) == len(flat_s)
    for a, s in zip(flat_a, flat_s):
        assert len(a) == len(s.shape), (a, s.shape)


def test_cache_axes_cover_every_leaf():
    cfg = get_config("jamba-v0.1-52b", reduced=True)
    model = build_model(cfg)
    spec = model.cache_spec(4, 64)
    axes = model.cache_axes(spec)
    flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    flat_s = jax.tree.leaves(spec)
    assert len(flat_a) == len(flat_s)


_SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.launch.programs import build_program
from repro.perf.hlo import collective_summary

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
prog = build_program("mixtral-8x7b", "train_4k", mesh, reduced=True)
with mesh:
    compiled = prog.lower().compile()
cs = collective_summary(compiled.as_text(), 8)
print("WIRE", cs["total_wire_bytes_per_chip"])
assert cs["count"] > 0, "multi-axis training must produce collectives"
print("OK")
"""


def test_multipod_lowering_smoke_subprocess():
    """Reduced mixtral train lowers+compiles on a (pod,data,model) mesh."""
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROC_SCRIPT, str(REPO / "src")],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "XLA_FLAGS": ""},
    )
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_compressed_dp_subprocess():
    """Int8 EF-compressed DP halves gradient wire bytes (4 host devices)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import build_model
from repro.optim.adamw import OptConfig
from repro.training.dp_compressed import init_state, make_dp_train_step
from repro.data.batches import make_batch
from repro.perf.hlo import collective_summary

mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
cfg = get_config("qwen2-0.5b", reduced=True)
model = build_model(cfg)
state = init_state(model, jax.random.PRNGKey(0))
batch = make_batch(jax.random.PRNGKey(1), cfg, batch=8, seq=32)
wires, losses = {}, {}
for compress in (False, True):
    step = make_dp_train_step(model, OptConfig(), mesh, compress=compress)
    with mesh:
        jitted = jax.jit(step)
        comp = jitted.lower(state, batch).compile()
        wires[compress] = collective_summary(comp.as_text(), 4)["total_wire_bytes_per_chip"]
        _, m = jitted(state, batch)
        losses[compress] = float(m["loss"])
assert wires[True] < 0.6 * wires[False], wires
assert abs(losses[True] - losses[False]) < 1e-2, losses
print("OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", script, str(REPO / "src")],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "XLA_FLAGS": ""},
    )
    assert "OK" in r.stdout, r.stdout + r.stderr
