"""Flash-decode for TPU: one new token per sequence against a (possibly
ring-buffer) KV cache.

Grid = (batch, k_block), k_block innermost with per-KV-head (m, l, acc)
streaming-softmax scratch — the same VMEM-resident pattern as
flash_attention but with Sq == 1 folded into the G query heads of each kv
group, and validity driven by the cache's pos_ids (slot -> absolute
position, -1 = empty) instead of a causal frontier, which makes it
correct for both linear and SWA ring caches.

The cache is read in its own order: k/v arrive as (B, Smax, K*hd), a
reshape of (B, Smax, K, hd) with no transpose. (Where XLA lays the cache
out Smax-minor, as for a (2, 64) minor pair, it still copies it into that
order before the call.) One grid step holds every KV head of one batch
row over ``block_k`` slots, and each head's scores come from its hd-lane
slice. ``kv_block`` picks ``block_k``: the whole cache when it fits the
VMEM budget, else the largest 128-multiple dividing Smax that does, so a
long cache streams through several blocks with the same scratch.
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

#: bytes of one K (or V) block. Double-buffered K and V blocks take four
#: times this, 4 MiB, a quarter of a v5e core's 16 MiB default scoped VMEM,
#: which leaves room for the f32 copies of one head's slice and the scores.
#: A block this size moves in about 1.3 us at 819 GB/s, several times the
#: fixed cost of a grid step.
KV_BLOCK_BYTES = 1 << 20


def kv_block(smax: int, kv_width: int, itemsize: int) -> int:
    """Cache slots per grid step for a (.., smax, kv_width) K/V cache.

    The whole cache when one row of it fits ``KV_BLOCK_BYTES``; else the
    largest multiple of 128 dividing ``smax`` that fits; else the smallest
    tile-legal block (128, or ``smax`` when 128 does not divide it).
    """
    row = kv_width * itemsize
    if smax * row <= KV_BLOCK_BYTES:
        return smax
    fits = [b for b in range(128, smax, 128)
            if smax % b == 0 and b * row <= KV_BLOCK_BYTES]
    if fits:
        return max(fits)
    return 128 if smax % 128 == 0 else smax


def _decode_kernel(
    len_ref,  # (B,) SMEM scalar prefetch: current absolute positions
    q_ref,  # (1, K, G, hd)
    k_ref,  # (1, bk, K*hd)
    v_ref,  # (1, bk, K*hd)
    pid_ref,  # (1, 1, bk) pos_ids of the slots
    o_ref,  # (1, K, G, hd)
    m_scr,  # (K, G, 1)
    l_scr,  # (K, G, 1)
    acc_scr,  # (K, G, hd)
    *,
    window: int,
    softcap: float,
    scale: float,
    num_k_blocks: int,
):
    ik = pl.program_id(1)
    K, _, hd = acc_scr.shape

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pid = pid_ref[0]  # (1, bk) int32
    qpos = len_ref[pl.program_id(0)]  # scalar int32

    valid = (pid >= 0) & (pid <= qpos)
    if window > 0:
        valid &= (qpos - pid) < window

    for g in range(K):
        lanes = slice(g * hd, (g + 1) * hd)
        q = q_ref[0, g].astype(F32)  # (G, hd)
        k = k_ref[0, :, lanes].astype(F32)  # (bk, hd)
        v = v_ref[0, :, lanes].astype(F32)  # (bk, hd)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
        )  # (G, bk)
        s = s * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[g, :, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))  # (G,)
        p = jnp.exp(s - m_new[:, None])  # (G, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[g, :, 0] = l_scr[g, :, 0] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
        )  # (G, hd)
        acc_scr[g] = acc_scr[g] * alpha[:, None] + pv
        m_scr[g, :, 0] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret"))
def decode_attention(
    q: jax.Array,  # (B, H, hd) the new token's queries
    k: jax.Array,  # (B, Smax, K, hd)
    v: jax.Array,  # (B, Smax, K, hd)
    pos_ids: jax.Array,  # (B, Smax) int32, -1 = empty slot
    lengths: jax.Array,  # (B,) int32 current position
    *,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    Smax, K = k.shape[1], k.shape[2]
    G = H // K
    block_k = kv_block(Smax, K * hd, k.dtype.itemsize)
    nk = Smax // block_k
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(B, K, G, hd)
    kr = k.reshape(B, Smax, K * hd)  # the cache's own order: no transpose
    vr = v.reshape(B, Smax, K * hd)
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
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((1, K, G, hd), lambda b, j, n: (b, 0, 0, 0)),
                pl.BlockSpec((1, block_k, K * hd), lambda b, j, n: (b, j, 0)),
                pl.BlockSpec((1, block_k, K * hd), lambda b, j, n: (b, j, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, j, n: (b, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, K, G, hd), lambda b, j, n: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, G, 1), F32),
                pltpu.VMEM((K, G, 1), F32),
                pltpu.VMEM((K, G, hd), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qr, kr, vr, pid)
    return out.reshape(B, H, hd)
