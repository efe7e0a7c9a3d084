"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), swept over
shapes, GQA ratios, dtypes, and masking variants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import KV_BLOCK_BYTES, decode_attention, kv_block
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import flash_attention_diff, sdpa_flash
from repro.kernels.ref import (
    decode_attention_ref,
    flash_attention_ref,
    ssd_scan_ref,
    ssd_sequential_ref,
)
from repro.kernels.ssd_scan import ssd_scan

# full Pallas sweeps run in interpret mode on CPU and dominate suite
# time; `pytest -m "not slow"` gives the fast tier-1 signal
pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


def _qkv(B, Sq, Sk, H, K, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, hd)).astype(dtype)
    return q, k, v


FLASH_CASES = [
    # B, Sq, H, K, hd, causal, window, softcap
    (2, 256, 8, 4, 64, True, 0, 0.0),
    (1, 384, 4, 2, 128, True, 128, 0.0),
    (2, 128, 8, 8, 64, True, 0, 50.0),  # MHA + gemma softcap
    (1, 256, 14, 2, 64, False, 0, 0.0),  # qwen2-ish GQA, non-causal
    (1, 256, 4, 1, 128, True, 0, 0.0),  # MQA
    (2, 256, 8, 4, 32, True, 256, 30.0),  # window >= S (no-op) + cap
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    B, S, H, K, hd, causal, win, cap = case
    q, k, v = _qkv(B, S, S, H, K, hd, dtype)
    out = flash_attention(
        q, k, v, causal=causal, window=win, softcap=cap, interpret=True
    )
    ref = flash_attention_ref(q, k, v, causal=causal, window=win, softcap=cap)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_grad_matches_oracle():
    q, k, v = _qkv(1, 128, 128, 4, 2, 64, jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention_diff(q, k, v, True, 0, 0.0) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(flash_attention_ref(q, k, v, causal=True) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


DECODE_CASES = [
    # B, H, K, hd, Smax, window, fill
    (2, 8, 4, 64, 256, 0, 100),
    (2, 4, 2, 128, 256, 128, 37),
    (1, 8, 1, 64, 512, 0, 511),  # MQA, nearly-full cache
    (3, 4, 4, 32, 128, 0, 0),  # empty-ish cache (only slot 0)
    (8, 14, 2, 64, 768, 0, 520),  # qwen2-0.5b served batch: one block per row
    (2, 16, 8, 128, 1024, 0, 900),  # K 8, hd 128: several blocks per row
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_oracle(case, dtype):
    B, H, K, hd, Smax, win, fill = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Smax, K, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Smax, K, hd)).astype(dtype)
    lengths = jnp.full((B,), fill, jnp.int32)
    pos = jnp.where(
        jnp.arange(Smax)[None] <= lengths[:, None], jnp.arange(Smax)[None], -1
    ).astype(jnp.int32)
    out = decode_attention(q, k, v, pos, lengths, window=win, interpret=True)
    ref = decode_attention_ref(q, k, v, pos, lengths, window=win)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def _ring_case(H, K, hd, Smax, start):
    """Ring-buffer slot order (wrapped positions) must not matter: absolute
    positions start..start+Smax-1 stored at slot p % Smax, window Smax."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, H, hd))
    k = jax.random.normal(ks[1], (1, Smax, K, hd))
    v = jax.random.normal(ks[2], (1, Smax, K, hd))
    abs_pos = jnp.arange(start, start + Smax)
    slots = abs_pos % Smax
    pos = jnp.zeros((1, Smax), jnp.int32).at[0, slots].set(abs_pos.astype(jnp.int32))
    lengths = jnp.array([start + Smax - 1], jnp.int32)
    out = decode_attention(q, k, v, pos, lengths, window=Smax, interpret=True)
    ref = decode_attention_ref(q, k, v, pos, lengths, window=Smax)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_decode_attention_ring_cache():
    _ring_case(H=4, K=2, hd=64, Smax=128, start=200)


def test_decode_attention_ring_cache_over_blocks():
    """Two 256-slot blocks with the wrap inside the first: the streaming
    softmax carries across them."""
    assert kv_block(512, 8 * 128, 4) == 256
    _ring_case(H=16, K=8, hd=128, Smax=512, start=600)


@pytest.mark.parametrize("smax,K,hd,itemsize", [
    (768, 2, 64, 2),  # qwen2-0.5b served cache
    (768, 2, 64, 4),
    (128, 1, 64, 2),
    (4096, 8, 128, 2),  # internlm2 / granite / mixtral at a long context
    (4096, 8, 128, 4),
    (8192, 4, 256, 2),  # gemma2
    (1152, 8, 128, 2),  # 1152 = 9 * 128: the largest fitting divisor is 384
])
def test_kv_block_tiles_the_cache(smax, K, hd, itemsize):
    bk = kv_block(smax, K * hd, itemsize)
    assert smax % bk == 0
    assert bk == smax or bk % 128 == 0
    assert bk * K * hd * itemsize <= KV_BLOCK_BYTES
    # no larger legal block fits the budget
    for b in range(bk + 128, smax + 1, 128):
        if smax % b == 0:
            assert b * K * hd * itemsize > KV_BLOCK_BYTES


def test_kv_block_is_the_whole_cache_at_the_served_shape():
    """qwen2-0.5b's served decode (Smax 768, K 2, hd 64, bf16): one grid
    step per batch row."""
    assert kv_block(768, 2 * 64, 2) == 768


SSD_CASES = [
    # B, S, H, P, N, chunk
    (2, 256, 4, 64, 32, 128),
    (1, 256, 2, 32, 64, 64),
    (2, 512, 2, 64, 128, 128),  # mamba2-2.7b-like head
    (1, 128, 8, 16, 16, 32),  # jamba-like small state
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_oracles(case, dtype):
    B, S, H, P, N, chunk = case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, H, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, H, N)) * 0.5).astype(dtype)
    yk, hk = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, hr = ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk)
    ys, hs = ssd_sequential_ref(x, dt, A, Bm, Cm)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(yk, np.float32), np.asarray(yr, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(yk, np.float32), np.asarray(ys, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hs), atol=tol, rtol=tol)


def test_ssd_chunk_invariance():
    """The chunked algorithm must be exactly chunk-size independent."""
    B, S, H, P, N = 1, 256, 2, 32, 32
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, H, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, H, N)) * 0.5
    outs = [ssd_scan_ref(x, dt, A, Bm, Cm, chunk=c)[0] for c in (32, 64, 128, 256)]
    for o in outs[1:]:
        # chunk-size independent up to f32 accumulation order
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=2e-4, rtol=1e-4)


def test_sdpa_flash_model_integration():
    """The registered 'pallas' impl matches 'jnp' inside a real model."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("granite-8b", reduced=True)
    mj = build_model(cfg, impl="jnp")
    mp = build_model(cfg, impl="pallas")
    params = mj.init(KEY)
    toks = jax.random.randint(KEY, (2, 128), 0, cfg.vocab_size)
    lj, _ = mj.forward(params, toks, dtype=jnp.float32)
    lp, _ = mp.forward(params, toks, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(lj), np.asarray(lp), atol=1e-3)
