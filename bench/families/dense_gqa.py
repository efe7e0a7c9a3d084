"""Dense decoder with grouped-query attention (Qwen2, InternLM2).

Published description: token embedding; per layer, RMSNorm, attention
with ``num_key_value_heads`` shared key/value heads, rotary position
embedding on half-split pairs (dimension i rotates with i + head_dim/2),
an optional bias on the q, k and v projections, a residual add, RMSNorm,
a SwiGLU MLP (down(silu(gate(x)) * up(x))) and a residual add; a final
RMSNorm and the output head (the embedding, transposed, where tied).

This file holds, for that description alone:

* ``PROGRAM_FIELDS``: which field of the program's ``ModelConfig`` each
  key of the configuration file sets. Set-up compares the built program
  with it, and a ``cut`` reaches the program through it;
* ``make_weights``: every weight from the seed, on the device, in one
  jitted call, in the published layout and the served dtype;
* ``to_program``: the same weights in the layout the served program
  reads. Its rotary embedding pairs dimensions (2i, 2i+1), so the q and k
  projections are permuted along the head dimension: the same function,
  exactly;
* ``reference_logits``: the plain float32 forward at matmul precision
  HIGHEST, one layer at a time inside a scan, with no cache and no
  kernel. ``quant="fp8"`` rounds every matmul's operands to float8 e4m3
  with per-tensor scales: the control, one precision below bf16;
* the model FLOPs of a prefill and of a decode step.

It imports nothing of the program under test.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: spread of the norm scales and q/k/v biases around their published
#: initial values (1 and 0): non-trivial, so the check sees them applied
NORM_SPREAD = 0.1
BIAS_SCALE = 0.5


#: ModelConfig field <- configuration file key (a dot steps into a group)
PROGRAM_FIELDS = {
    "num_layers": "num_hidden_layers",
    "d_model": "hidden_size",
    "num_heads": "num_attention_heads",
    "num_kv_heads": "num_key_value_heads",
    "head_dim": "model.head_dim",
    "d_ff": "intermediate_size",
    "vocab_size": "vocab_size",
    "qkv_bias": "model.qkv_bias",
    "tie_embeddings": "tie_word_embeddings",
    "rope_theta": "rope_theta",
    "norm_eps": "rms_norm_eps",
}


def dims(config: dict) -> dict:
    """The sizes the family needs, read from a configuration file."""
    m = config["model"]
    D = int(config["hidden_size"])
    H = int(config["num_attention_heads"])
    return {
        "L": int(config["num_hidden_layers"]),
        "D": D,
        "H": H,
        "K": int(config["num_key_value_heads"]),
        "hd": int(m.get("head_dim", D // H)),
        "F": int(config["intermediate_size"]),
        "V": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "tied": bool(config["tie_word_embeddings"]),
        "bias": bool(m["qkv_bias"]),
        "dtype": m["dtype"],
    }


def _shapes(d: dict) -> dict:
    """(shape, kind, fan_in) of every weight, published layout."""
    L, D, H, K, hd, F, V = (d[k] for k in ("L", "D", "H", "K", "hd", "F", "V"))
    layer = {
        "ln1": ((L, D), "norm", 0),
        "wq": ((L, D, H, hd), "w", D),
        "wk": ((L, D, K, hd), "w", D),
        "wv": ((L, D, K, hd), "w", D),
        "wo": ((L, H, hd, D), "w", H * hd),
        "ln2": ((L, D), "norm", 0),
        "w_gate": ((L, D, F), "w", D),
        "w_up": ((L, D, F), "w", D),
        "w_down": ((L, F, D), "w", F),
    }
    if d["bias"]:
        layer |= {
            "bq": ((L, H, hd), "bias", 0),
            "bk": ((L, K, hd), "bias", 0),
            "bv": ((L, K, hd), "bias", 0),
        }
    tree = {
        "embed": ((V, D), "w", D),
        "layers": layer,
        "final_norm": ((D,), "norm", 0),
    }
    if not d["tied"]:
        tree["lm_head"] = ((D, V), "w", D)
    return tree


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as the two words of a threefry key."""
    s = int(seed) % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], dtype=np.uint32)


def _flat(tree: dict, prefix: str = "") -> list:
    out = []
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out += _flat(v, f"{prefix}{k}/")
        else:
            out.append((f"{prefix}{k}", v))
    return out


def _make(d: dict, words) -> dict:
    key = jax.random.wrap_key_data(words)
    dtype = jnp.dtype(d["dtype"])
    out: dict = {}
    for i, (path, (shape, kind, fan_in)) in enumerate(_flat(_shapes(d))):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
        if kind == "norm":
            w = 1.0 + NORM_SPREAD * z
        elif kind == "bias":
            w = BIAS_SCALE * z
        else:
            w = z / math.sqrt(fan_in)
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = w.astype(dtype)
    return out


def _interleave(w: jax.Array, hd: int) -> jax.Array:
    """Half-split head dimension -> interleaved pairs: slot 2i takes
    dimension i, slot 2i+1 takes dimension i + hd/2."""
    perm = np.arange(hd).reshape(2, hd // 2).T.reshape(-1)
    return jnp.take(w, perm, axis=-1)


def _program_layout(d: dict, w: dict) -> dict:
    lw, hd = w["layers"], d["hd"]
    attn = {
        "wq": _interleave(lw["wq"], hd),
        "wk": _interleave(lw["wk"], hd),
        "wv": lw["wv"],
        "wo": lw["wo"],
    }
    if d["bias"]:
        attn |= {"bq": _interleave(lw["bq"], hd),
                 "bk": _interleave(lw["bk"], hd), "bv": lw["bv"]}
    out = {
        "embed": w["embed"],
        "blocks": {"sub0": {
            "ln1": lw["ln1"], "attn": attn, "ln2": lw["ln2"],
            "mlp": {"wi": lw["w_up"], "wg": lw["w_gate"], "wo": lw["w_down"]},
        }},
        "final_norm": w["final_norm"],
    }
    if not d["tied"]:
        out["lm_head"] = w["lm_head"]
    return out


@functools.lru_cache(maxsize=None)
def _make_jit(frozen: tuple, program: bool):
    d = dict(frozen)

    def fn(words):
        w = _make(d, words)
        return _program_layout(d, w) if program else w

    return jax.jit(fn)


def make_weights(config: dict, seed: int, program: bool = False) -> dict:
    """Every weight from the seed, in the served dtype, on the device.
    ``program=True`` returns them in the served program's layout."""
    d = dims(config)
    return _make_jit(tuple(sorted(d.items())), program)(seed_words(seed))


# --- the plain reference ---------------------------------------------------

def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one per-tensor scale (max |x| -> 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(spec: str, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Half-split rotary embedding. x: (S, N, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq  # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(d: dict, quant, x, p):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    S = x.shape[0]
    G = d["H"] // d["K"]
    h = _rms(x, p["ln1"], d["eps"])
    q = _mm("sd,dhk->shk", h, p["wq"], quant)
    k = _mm("sd,dhk->shk", h, p["wk"], quant)
    v = _mm("sd,dhk->shk", h, p["wv"], quant)
    if d["bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("qhk,shk->hqs", q, k, precision=HI) / math.sqrt(d["hd"])
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqs,shk->qhk", a, v, precision=HI)
    x = x + _mm("qhk,hkd->qd", o, p["wo"], quant)
    h = _rms(x, p["ln2"], d["eps"])
    g = _mm("sd,df->sf", h, p["w_gate"], quant)
    u = _mm("sd,df->sf", h, p["w_up"], quant)
    x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], quant)
    return x, None


@functools.lru_cache(maxsize=None)
def _reference_jit(frozen: tuple, start: int, quant):
    d = dict(frozen)

    def fn(w, tokens):
        x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
        x, _ = jax.lax.scan(functools.partial(_layer, d, quant), x, w["layers"])
        x = _rms(x[start:], w["final_norm"].astype(F32), d["eps"])
        head = w["embed"].T if d["tied"] else w["lm_head"]
        return _mm("sd,dv->sv", x, head.astype(F32), quant)

    return jax.jit(fn)


def reference_logits(config: dict, weights: dict, tokens: np.ndarray,
                     start: int, quant=None) -> jax.Array:
    """Float32 logits at positions ``start..len(tokens)-1`` of one row.
    ``weights`` come from ``make_weights(config, seed)`` (published
    layout); ``quant="fp8"`` gives the control."""
    d = dims(config)
    fn = _reference_jit(tuple(sorted(d.items())), int(start), quant)
    return fn(weights, jnp.asarray(tokens, jnp.int32))


# --- model FLOPs -------------------------------------------------------------

def matmul_params(config: dict) -> tuple[int, int]:
    """Weights in the per-layer matmuls (all layers), and in the head."""
    d = dims(config)
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return d["L"] * per_layer, D * d["V"]


def prefill_flops(config: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one prefill: every matmul over the prompt, causal
    attention, and the head at the last position only (as served)."""
    d = dims(config)
    layers, head = matmul_params(config)
    attn = 4 * d["H"] * d["hd"] * seq * (seq + 1) / 2 * d["L"]
    return batch * (2.0 * seq * layers + attn + 2.0 * head)


def decode_flops(config: dict, batch: int, pos: int) -> float:
    """Model FLOPs of one decode step of the token at position ``pos``
    (it attends to pos + 1 keys)."""
    d = dims(config)
    layers, head = matmul_params(config)
    attn = 4 * d["H"] * d["hd"] * (pos + 1) * d["L"]
    return batch * (2.0 * layers + attn + 2.0 * head)
