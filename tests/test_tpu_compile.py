"""The Pallas kernels compile for a described TPU v5e at real widths.

Nothing runs: each test lowers a kernel with interpret=False for one chip
of a ``v5e:2x2`` topology that is described, not attached, and asserts
the compiled program holds the kernel (``tpu_custom_call``). This is
what interpret mode cannot show — tile-illegal block shapes and VMEM
overruns are refused here exactly as on the chip.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the test workers
must all collect the same tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention, kv_block
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

QWEN = get_config("qwen2-0.5b")  # 14 query heads, 2 KV heads, head dim 64
MAMBA = get_config("mamba2-2.7b")
INTERNLM = get_config("internlm2-1.8b")  # 16 query heads, 8 KV heads, head dim 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("batch,seq", [(1, 128), (4, 512)])
def test_flash_attention_compiles_at_qwen2_prefill(one_chip, batch, seq):
    H, K, hd = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    text = _compiled_text(
        fn, one_chip,
        ((batch, seq, H, hd), jnp.bfloat16),
        ((batch, seq, K, hd), jnp.bfloat16),
        ((batch, seq, K, hd), jnp.bfloat16),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [1, 4, 8])
def test_decode_attention_compiles_at_qwen2_decode(one_chip, batch):
    H, K, hd = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    smax = 768  # the served cache: prompt 512 + output 16, rounded, + 128
    fn = functools.partial(decode_attention, interpret=False)
    text = _compiled_text(
        fn, one_chip,
        ((batch, H, hd), jnp.bfloat16),
        ((batch, smax, K, hd), jnp.bfloat16),
        ((batch, smax, K, hd), jnp.bfloat16),
        ((batch, smax), jnp.int32),
        ((batch,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_decode_attention_compiles_over_several_blocks(one_chip):
    """K 8 / hd 128 at a 4096-slot cache streams through 512-slot blocks:
    the lane slices are 128-aligned and the blocks fit VMEM."""
    B, H, K, hd = 2, INTERNLM.num_heads, INTERNLM.num_kv_heads, INTERNLM.head_dim
    smax = 4096
    assert kv_block(smax, K * hd, 2) < smax
    fn = functools.partial(decode_attention, window=1024, interpret=False)
    text = _compiled_text(
        fn, one_chip,
        ((B, H, hd), jnp.bfloat16),
        ((B, smax, K, hd), jnp.bfloat16),
        ((B, smax, K, hd), jnp.bfloat16),
        ((B, smax), jnp.int32),
        ((B,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles_over_several_chunks(one_chip):
    B, S = 1, 512  # four 128-token chunks
    H, P, N = MAMBA.ssm_heads, MAMBA.ssm_head_dim, MAMBA.ssm_state
    fn = functools.partial(ssd_scan, chunk=128, interpret=False)
    text = _compiled_text(
        fn, one_chip,
        ((B, S, H, P), jnp.bfloat16),
        ((B, S, H), jnp.float32),
        ((H,), jnp.float32),
        ((B, S, H, N), jnp.bfloat16),
        ((B, S, H, N), jnp.bfloat16),
    )
    assert "tpu_custom_call" in text
