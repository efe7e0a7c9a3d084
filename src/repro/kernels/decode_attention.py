"""Flash-decode for TPU: one new token per sequence against a (possibly
ring-buffer) KV cache.

Grid = (batch, kv_head, k_block), k_block innermost with (m, l, acc)
streaming-softmax scratch — the same VMEM-resident pattern as
flash_attention but with Sq == 1 folded into the G query heads of each kv
group, and validity driven by the cache's pos_ids (slot -> absolute
position, -1 = empty) instead of a causal frontier, which makes it
correct for both linear and SWA ring caches.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _decode_kernel(
    len_ref,  # (B,) SMEM scalar prefetch: current absolute positions
    q_ref,  # (1, 1, G, hd)
    k_ref,  # (1, 1, bk, hd)
    v_ref,  # (1, 1, bk, hd)
    pid_ref,  # (1, 1, bk) pos_ids of the slots
    o_ref,  # (1, 1, G, hd)
    m_scr,  # (G, 1)
    l_scr,  # (G, 1)
    acc_scr,  # (G, hd)
    *,
    window: int,
    softcap: float,
    scale: float,
    num_k_blocks: int,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(F32)  # (G, hd)
    k = k_ref[0, 0].astype(F32)  # (bk, hd)
    v = v_ref[0, 0].astype(F32)  # (bk, hd)
    pid = pid_ref[0]  # (1, bk) int32
    qpos = len_ref[pl.program_id(0)]  # scalar int32

    valid = (pid >= 0) & (pid <= qpos)
    if window > 0:
        valid &= (qpos - pid) < window

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
    )  # (G, bk)
    s = s * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[..., 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))  # (G,)
    p = jnp.exp(s - m_new[:, None])  # (G, bk)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[..., 0] = l_scr[..., 0] * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )  # (G, hd)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
    m_scr[..., 0] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[..., 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "block_k", "interpret")
)
def decode_attention(
    q: jax.Array,  # (B, H, hd) the new token's queries
    k: jax.Array,  # (B, Smax, K, hd)
    v: jax.Array,  # (B, Smax, K, hd)
    pos_ids: jax.Array,  # (B, Smax) int32, -1 = empty slot
    lengths: jax.Array,  # (B,) int32 current position
    *,
    window: int = 0,
    softcap: float = 0.0,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    Smax, K = k.shape[1], k.shape[2]
    G = H // K
    assert Smax % block_k == 0, (Smax, block_k)
    nk = Smax // block_k
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(B, K, G, hd)
    kr = jnp.moveaxis(k, 1, 2)  # (B, K, Smax, hd)
    vr = jnp.moveaxis(v, 1, 2)
    # a unit middle axis keeps the pos_ids block's last two dims
    # (1, block_k) tile-legal for any B; lengths ride in SMEM
    pid = pos_ids.reshape(B, 1, Smax)

    kernel = functools.partial(
        _decode_kernel,
        window=window,
        softcap=softcap,
        scale=scale,
        num_k_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, nk),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, j, n: (b, h, 0, 0)),
                pl.BlockSpec(
                    (1, 1, block_k, hd), lambda b, h, j, n: (b, h, j, 0)
                ),
                pl.BlockSpec(
                    (1, 1, block_k, hd), lambda b, h, j, n: (b, h, j, 0)
                ),
                pl.BlockSpec((1, 1, block_k), lambda b, h, j, n: (b, 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, G, hd), lambda b, h, j, n: (b, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((G, 1), F32),
                pltpu.VMEM((G, 1), F32),
                pltpu.VMEM((G, hd), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qr, kr, vr, pid)
    return out.reshape(B, H, hd)
