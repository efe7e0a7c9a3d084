"""Core neural layers shared by all 10 architectures.

All functions are pure; parameters come from declarative ``ParamDecl``
trees (see params.py). Activations are annotated with logical sharding
axes via ``parallel.sharding.shard`` so the same model code lowers on a
single CPU device (no-op) and on the 512-chip production mesh.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from ..parallel.sharding import shard
from .config import ModelConfig
from .params import ParamDecl


def _coll_out(x):
    """Tag row-parallel (all-reduced) outputs so the "coll" remat policy
    can save exactly these and avoid re-running forward collectives in
    the backward pass (see EXPERIMENTS.md SPerf, mixtral train)."""
    return checkpoint_name(x, "coll_out")

F32 = jnp.float32

# Pluggable scaled-dot-product-attention implementations. kernels/ops.py
# registers "pallas" (sdpa_impl imports it on first use); "jnp" is the
# oracle/default.
SDPA_IMPL: dict = {}


# ---------------------------------------------------------------------------
# Norms / activations / rope
# ---------------------------------------------------------------------------

def rms_norm(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(F32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (w.astype(F32) * xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def activate(x: jax.Array, act: str) -> jax.Array:
    return jax.nn.gelu(x) if act == "gelu" else jax.nn.silu(x)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> jax.Array:
    """(.., hd/2) rotation angles for given absolute positions."""
    freq = theta ** (-jnp.arange(0, head_dim // 2, dtype=F32) / (head_dim // 2))
    return positions.astype(F32)[..., None] * freq  # (..., hd/2)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, interleaved: bool) -> jax.Array:
    """Rotary embedding. x: (B, S, N, hd); positions: (B, S).

    Interleaved pairing (2i, 2i+1) keeps rotation pairs local under
    head_dim tensor-parallel sharding (shards hold even-sized contiguous
    chunks >= 2), unlike the rotate-half formulation.
    """
    B, S, N, hd = x.shape
    ang = rope_angles(positions, hd, theta)[:, :, None, :]  # (B,S,1,hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    if interleaved:
        x1 = xf[..., 0::2]
        x2 = xf[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x1 * sin + x2 * cos
        out = jnp.stack([r1, r2], axis=-1).reshape(B, S, N, hd)
    else:
        half = hd // 2
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attn_decl(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    decl = {
        "wq": ParamDecl((d, h, hd), ("fsdp", "heads", "q_param_hd"), fan_in=d),
        "wk": ParamDecl((d, k, hd), ("fsdp", "kv_heads", "kv_param_hd"), fan_in=d),
        "wv": ParamDecl((d, k, hd), ("fsdp", "kv_heads", "kv_param_hd"), fan_in=d),
        "wo": ParamDecl((h, hd, d), ("heads", "head_dim", "fsdp"), fan_in=h * hd),
    }
    if cfg.qkv_bias and not cross:
        decl["bq"] = ParamDecl((h, hd), ("heads", "head_dim"), init="zeros")
        decl["bk"] = ParamDecl((k, hd), ("kv_heads", "head_dim"), init="zeros")
        decl["bv"] = ParamDecl((k, hd), ("kv_heads", "head_dim"), init="zeros")
    return decl


def causal_window_mask(
    q_pos: jax.Array,  # (B, Sq) absolute positions of queries
    k_pos: jax.Array,  # (B, Sk) absolute positions of keys (-1 = empty slot)
    window: jax.Array | int | None,  # traced or static; <=0 / None = global
    causal: bool = True,
) -> jax.Array:
    d = q_pos[:, :, None] - k_pos[:, None, :]  # (B, Sq, Sk)
    ok = k_pos[:, None, :] >= 0
    if causal:
        ok &= d >= 0
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        ok &= (w <= 0) | (d < w)
    return ok


def _sdpa_dense(q, k, v, q_pos, k_pos, window, causal, cap) -> jax.Array:
    """Materialized-scores attention: (B,Sq,H,hd) x (B,Sk,K,hd)."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=F32)
    scores = scores / math.sqrt(hd)
    scores = softcap(scores, cap)
    mask = causal_window_mask(q_pos, k_pos, window, causal)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, hd)


#: chunk the query axis when the full score tensor would exceed this many
#: elements per (batch, head) pair — the jnp analogue of flash attention.
_CHUNK_BUDGET = 1 << 20
_CHUNK_MIN_SQ = 1024


def _sdpa_jnp(q, k, v, q_pos, k_pos, window, causal, cap) -> jax.Array:
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if Sq < _CHUNK_MIN_SQ or Sq * Sk <= _CHUNK_BUDGET:
        return _sdpa_dense(q, k, v, q_pos, k_pos, window, causal, cap)
    chunk = max(128, _CHUNK_BUDGET // Sk)
    while Sq % chunk:
        chunk //= 2
    nq = Sq // chunk
    qr = jnp.moveaxis(q.reshape(B, nq, chunk, H, hd), 1, 0)  # (nq,B,c,H,hd)
    pr = jnp.moveaxis(q_pos.reshape(B, nq, chunk), 1, 0)  # (nq,B,c)

    def body(_, inp):
        qc, pc = inp
        # checkpoint: recompute this chunk's scores in backward instead of
        # stashing (nq, B, H, chunk, Sk) residuals == the full score matrix
        return None, _sdpa_dense(qc, k, v, pc, k_pos, window, causal, cap)

    _, outs = jax.lax.scan(jax.checkpoint(body, prevent_cse=False), None, (qr, pr))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, hd)


SDPA_IMPL["jnp"] = _sdpa_jnp


def sdpa_impl(impl: str):
    """The SDPA implementation registered as ``impl``. Asking for
    "pallas" imports kernels/ops.py, which registers it; any other name
    that nobody registered raises instead of running the jnp path."""
    if impl == "pallas" and impl not in SDPA_IMPL:
        from ..kernels import ops  # noqa: F401 — registers "pallas"
    try:
        return SDPA_IMPL[impl]
    except KeyError:
        raise ValueError(
            f"unknown attention impl {impl!r}; known: {sorted(SDPA_IMPL)}"
        ) from None


def sdpa(q, k, v, *, q_pos, k_pos, window, causal, cap, impl: str = "jnp"):
    return sdpa_impl(impl)(q, k, v, q_pos, k_pos, window, causal, cap)


def quantize_kv(t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(slot, head) symmetric int8 over head_dim. t: (B,S,K,hd)."""
    amax = jnp.max(jnp.abs(t.astype(F32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(t.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0].astype(F32)  # (B,S,K,hd) s8, (B,S,K) f32


def dequantize_kv(q: jax.Array, scale: jax.Array, dt) -> jax.Array:
    return (q.astype(F32) * scale[..., None]).astype(dt)


def attention(
    p: dict,
    x: jax.Array,  # (B, S, D)
    *,
    cfg: ModelConfig,
    positions: jax.Array,  # (B, S)
    window: jax.Array | int | None = None,
    cache: Optional[dict] = None,  # {"k","v","pos_ids"} per-layer slices
    lengths: Optional[jax.Array] = None,  # (B,) current lengths (decode)
    kv_override: Optional[tuple] = None,  # cross-attn: (k, v, k_pos) precomputed
    causal: bool = True,
    use_rope: bool = True,
    impl: str = "jnp",
    kv_quant: bool = False,
):
    """Unified attention for train/prefill/decode/cross.

    Returns (out, new_cache). new_cache is None unless a cache was given
    or prefill requested one via cache={} sentinel. With kv_quant the
    cache stores int8 K/V (+ per-slot-head f32 scales): memory-bound
    decode reads half the bytes; dequantization fuses into the sdpa
    loads (EXPERIMENTS.md §Perf D).
    """
    B, S, D = x.shape
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    q = shard(q, "batch", "seq", "act_heads", "act_head_dim")

    if kv_override is not None:
        k, v, k_pos = kv_override
        new_cache = None
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
        if "bk" in p:
            k = k + p["bk"].astype(dt)
            v = v + p["bv"].astype(dt)
        if use_rope:
            k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_interleaved)
        k = shard(k, "batch", "seq", "act_kv_heads", "act_head_dim")
        v = shard(v, "batch", "seq", "act_kv_heads", "act_head_dim")
        if cache is not None and ("k" in cache or "k_q" in cache):
            # decode: write the S new entries (S==1) into ring/linear slots
            quant = "k_q" in cache
            Smax = (cache["k_q"] if quant else cache["k"]).shape[1]
            slot = (lengths[:, None] + jnp.arange(S)[None, :]) % Smax  # (B,S)
            oh = jax.nn.one_hot(slot, Smax, dtype=F32)  # (B,S,Smax)
            wrote = oh.sum(1) > 0  # (B, Smax) bool
            written = jnp.einsum(
                "bsm,bs->bm", oh.astype(jnp.int32), positions.astype(jnp.int32)
            )
            pos_ids = jnp.where(wrote, written, cache["pos_ids"])
            if quant:
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                sel = wrote[:, :, None, None]
                # S == 1 on this path: broadcast the new entry to all slots
                # and select only the written one
                ck = jnp.where(sel, kq[:, 0:1], cache["k_q"])
                cv = jnp.where(sel, vq[:, 0:1], cache["v_q"])
                cks = jnp.where(wrote[:, :, None], ks[:, 0:1], cache["k_s"])
                cvs = jnp.where(wrote[:, :, None], vs[:, 0:1], cache["v_s"])
                new_cache = {"k_q": ck, "v_q": cv, "k_s": cks, "v_s": cvs,
                             "pos_ids": pos_ids}
                k = dequantize_kv(ck, cks, dt)
                v = dequantize_kv(cv, cvs, dt)
                k_pos = pos_ids
            else:
                ohd = oh.astype(dt)
                ck = cache["k"] * (1 - ohd.sum(1)[:, :, None, None])
                cv = cache["v"] * (1 - ohd.sum(1)[:, :, None, None])
                ck = ck + jnp.einsum("bsm,bshk->bmhk", ohd, k)
                cv = cv + jnp.einsum("bsm,bshk->bmhk", ohd, v)
                new_cache = {"k": ck, "v": cv, "pos_ids": pos_ids}
                k, v, k_pos = ck, cv, pos_ids
        elif cache is not None:
            # prefill requested a cache: keys are their own slots
            if kv_quant:
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                new_cache = {"k_q": kq, "v_q": vq, "k_s": ks, "v_s": vs,
                             "pos_ids": positions}
                # serve exactly what decode will read (quantized)
                k = dequantize_kv(kq, ks, dt)
                v = dequantize_kv(vq, vs, dt)
            else:
                new_cache = {"k": k, "v": v, "pos_ids": positions}
            k_pos = positions
        else:
            new_cache = None
            k_pos = positions

    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_interleaved)
    out = sdpa(
        q, k, v,
        q_pos=positions, k_pos=k_pos, window=window, causal=causal,
        cap=cfg.attn_logit_softcap, impl=impl,
    )
    if cfg.attn_out_scale is not None:
        out = out * cfg.attn_out_scale
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    y = _coll_out(shard(y, "batch", "seq", "embed"))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def mlp_decl(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "wi": ParamDecl((d, f), ("fsdp", "ff"), fan_in=d),
        "wg": ParamDecl((d, f), ("fsdp", "ff"), fan_in=d),
        "wo": ParamDecl((f, d), ("ff", "fsdp"), fan_in=f),
    }


def mlp_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(dt))
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(dt))
    h = activate(g, cfg.act) * h
    h = shard(h, "batch", "seq", "ff")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(dt))
    return _coll_out(shard(y, "batch", "seq", "embed"))


def moe_decl(cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    # Axis priority: experts claim "model" when divisible (EP, e.g. 16
    # experts on a 16-way axis); otherwise the fallback lets "ff" claim it
    # (TP-MoE, e.g. mixtral's 8 experts on a 16-way axis). See sharding.py.
    return {
        "router": ParamDecl((d, e), ("fsdp", None), fan_in=d),
        "wi": ParamDecl((e, d, f), ("experts", "fsdp", "moe_ff"), fan_in=d),
        "wg": ParamDecl((e, d, f), ("experts", "fsdp", "moe_ff"), fan_in=d),
        "wo": ParamDecl((e, f, d), ("experts", "moe_ff", "fsdp"), fan_in=f),
    }


def moe_capacity(tokens: int, k: int, e: int, cf: float) -> int:
    c = int(math.ceil(tokens * k * cf / e))
    return max(8, -(-c // 8) * 8)  # round up to 8 lanes


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig):
    """Token-choice top-k MoE with GROUP-LOCAL sort-based dispatch.

    Routing groups are batch rows, so dispatch gathers/scatters stay inside
    the data shard (no global token all-gather; the only cross-device
    traffic is the expert-parallel all-to-all induced by resharding the
    (group, expert, capacity, d) tensor from batch- to expert-sharded).
    A naive globally-flattened dispatch was measured at ~8 TB/chip of
    all-gather on mixtral train_4k — see EXPERIMENTS.md §Perf.

    Returns (y, aux_loss). Dropless up to capacity_factor per group.
    """
    B, S, D = x.shape
    if S == 1 and B <= 16 and cfg.num_experts % 16 != 0:
        # tiny-batch decode: gather ONLY the top-k experts' weights.
        # The capacity path streams every expert's weights per step -
        # measured 3.5x excess HBM traffic on mixtral long_500k decode
        # (EXPERIMENTS.md SPerf C2). Gated to archs whose experts cannot
        # shard the 16-way model axis (mixtral: E=8 -> weights local);
        # for EP-sharded experts (jamba/phi3.5: E=16) the gather crosses
        # devices and was measured 3.6x SLOWER than capacity dispatch.
        return _moe_gathered(p, x, cfg)
    if S == 1:  # decode: one group over the (small) batch
        y, aux = _moe_grouped(p, x.reshape(1, B, D), cfg)
        return y.reshape(B, S, D), aux
    y, aux = _moe_grouped(p, x, cfg)
    return y, aux


def _moe_gathered(p: dict, x: jax.Array, cfg: ModelConfig):
    """Dropless per-token expert-weight gather; exact for any batch, used
    when weight streaming (not compute) dominates. x: (B, 1, D)."""
    B, S, D = x.shape
    dt = x.dtype
    K = cfg.top_k
    xf = x[:, 0]  # (B, D)
    logits = jnp.einsum("bd,de->be", xf.astype(F32), p["router"].astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)  # (B, K)
    gate = (gate / jnp.sum(gate, axis=-1, keepdims=True)).astype(dt)
    wi = jnp.take(p["wi"], eidx, axis=0).astype(dt)  # (B, K, D, F)
    wg = jnp.take(p["wg"], eidx, axis=0).astype(dt)
    wo = jnp.take(p["wo"], eidx, axis=0).astype(dt)  # (B, K, F, D)
    h = jnp.einsum("bd,bkdf->bkf", xf, wi)
    g = jnp.einsum("bd,bkdf->bkf", xf, wg)
    h = activate(g, cfg.act) * h
    y = jnp.einsum("bkf,bkfd->bd", h * gate[..., None], wo)
    aux = jnp.zeros((), F32)  # no aux loss on the decode path
    return y[:, None, :], aux


def _moe_grouped(p: dict, xg: jax.Array, cfg: ModelConfig):
    """xg: (G, T, D) — G routing groups of T tokens each."""
    G, T, D = xg.shape
    dt = xg.dtype
    E, K = cfg.num_experts, cfg.top_k
    C = moe_capacity(T, K, E, cfg.capacity_factor)

    logits = jnp.einsum("gtd,de->gte", xg.astype(F32), p["router"].astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)  # (G, T, E)
    gate, eidx = jax.lax.top_k(probs, K)  # (G, T, K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    flat_e = eidx.reshape(G, T * K)
    order = jnp.argsort(flat_e, axis=-1)  # (G, T*K) slot ids sorted by expert
    counts = jnp.sum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32), axis=1)  # (G, E)
    starts = jnp.cumsum(counts, axis=-1) - counts  # exclusive (G, E)
    pos = starts[:, :, None] + jnp.arange(C, dtype=jnp.int32)[None, None, :]
    valid = jnp.arange(C, dtype=jnp.int32)[None, None, :] < counts[:, :, None]
    slot = jnp.take_along_axis(
        order, jnp.minimum(pos, T * K - 1).reshape(G, E * C), axis=-1
    )  # (G, E*C)
    token = slot // K

    xe = jnp.take_along_axis(xg, token[..., None], axis=1)  # (G, E*C, D)
    xe = xe.reshape(G, E, C, D) * valid[..., None].astype(dt)
    xe = shard(xe, "batch", "experts", "capacity", "embed")
    h = jnp.einsum("gecd,edf->gecf", xe, p["wi"].astype(dt))
    g_ = jnp.einsum("gecd,edf->gecf", xe, p["wg"].astype(dt))
    h = activate(g_, cfg.act) * h
    h = shard(h, "batch", "experts", "capacity", "moe_ff")
    ye = jnp.einsum("gecf,efd->gecd", h, p["wo"].astype(dt))  # (G, E, C, D)
    ye = _coll_out(ye)  # direct output of the row-parallel partial-sum einsum

    gate_gc = jnp.take_along_axis(gate.reshape(G, T * K), slot, axis=-1)
    gate_gc = jnp.where(valid.reshape(G, E * C), gate_gc, 0.0)
    contrib = ye.reshape(G, E * C, D) * gate_gc[..., None].astype(dt)

    def scatter_row(tok, c):  # (E*C,), (E*C, D)
        return jnp.zeros((T, D), dt).at[tok].add(c)

    y = jax.vmap(scatter_row)(token, contrib)  # (G, T, D)
    y = _coll_out(shard(y, "batch", "seq", "embed"))

    # load-balancing aux loss (Switch/Mixtral formulation), averaged over groups
    me = jnp.mean(probs, axis=1)  # (G, E)
    assign = counts.astype(F32) / (T * K)  # (G, E)
    aux = E * jnp.mean(jnp.sum(me * assign, axis=-1))
    return y, aux
