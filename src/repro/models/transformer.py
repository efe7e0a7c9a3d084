"""Unified LM covering all 10 assigned architectures.

Depth is organized as ``n_super`` super-layers of ``period`` sublayers and
scanned with ``jax.lax.scan`` so the HLO contains each distinct sublayer
body exactly once (keeps multi-pod compiles tractable). Uniform archs have
period == 1; gemma2's local/global alternation gives period == 2; jamba's
mamba/attention 7:1 interleave with alternating dense/MoE FFNs gives
period == 8. Encoder-decoder (seamless) adds an encoder stack and
cross-attention to every decoder sublayer.

Cache layout (decode-ready):
  {"lengths": (B,), "blocks": <stacked per-super self caches>,
   "cross": <stacked cross-KV, enc-dec only>}
Cross-KV is read-only during decode, so it rides through the layer scan as
`xs` (never re-emitted as `ys`) — XLA does not copy it per step.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard
from .config import ModelConfig
from .layers import (
    attention,
    attn_decl,
    mlp_apply,
    mlp_decl,
    moe_apply,
    moe_decl,
    rms_norm,
    sdpa_impl,
    softcap,
)
from .params import ParamDecl, axes_tree, init_tree, shape_tree, stacked
from .ssd import mamba_apply, mamba_cache_decl, mamba_decl

F32 = jnp.float32


def ce_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy; logits (B,S,V) f32, targets (B,S)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


#: sequence-chunk the LM-head + CE when S exceeds this: the full (B,S,V)
#: logits tensor (and its gradient) never materializes in HBM.
_CE_CHUNK = 512


def chunked_ce(head_fn, x: jax.Array, targets: jax.Array) -> jax.Array:
    """CE over head_fn(x-chunk) with rematerialized chunks. x: (B,S,D)."""
    B, S, D = x.shape
    if S <= 2 * _CE_CHUNK:
        return ce_loss(head_fn(x), targets)
    c = _CE_CHUNK
    while S % c:
        c //= 2
    nc = S // c
    xr = jnp.moveaxis(x.reshape(B, nc, c, D), 1, 0)  # (nc,B,c,D)
    tr = jnp.moveaxis(targets.reshape(B, nc, c), 1, 0)

    def body(acc, inp):
        xc, tc = inp
        logits = head_fn(xc)  # (B,c,V) f32
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(logz - gold), None

    acc, _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), jnp.zeros((), F32), (xr, tr)
    )
    return acc / (B * S)


class LM:
    """Decoder-only / hybrid / enc-dec language model."""

    def __init__(self, cfg: ModelConfig, impl: str = "jnp", scan_unroll: bool = False,
                 kv_quant: bool = False):
        self.cfg = cfg
        sdpa_impl(impl)  # unknown names raise here; "pallas" registers
        self.impl = impl
        self.kv_quant = kv_quant  # int8 KV cache (serving)
        # unroll=True inlines every layer into the HLO: used by the
        # roofline differencing builds (perf/), where collectives inside a
        # rolled `while` body would be counted once regardless of depth
        self.scan_unroll = scan_unroll
        if cfg.is_hybrid:
            self.period = cfg.hybrid_period
        elif cfg.local_global_pattern:
            self.period = len(cfg.local_global_pattern)
        else:
            self.period = 1
        assert cfg.num_layers % self.period == 0, (cfg.num_layers, self.period)
        self.n_super = cfg.num_layers // self.period
        self.kinds = cfg.layer_kinds()[: self.period]
        self.ffns = cfg.ffn_kinds()[: self.period]
        self.windows = cfg.window_pattern()[: self.period]
        self.has_ffn = cfg.d_ff > 0

    # ------------------------------------------------------------------
    # Declarations
    # ------------------------------------------------------------------
    def _sub_decl(self, i: int, cross: bool) -> dict:
        cfg = self.cfg
        d = {"ln1": ParamDecl((cfg.d_model,), ("embed",), init="ones")}
        if self.kinds[i] == "attn":
            d["attn"] = attn_decl(cfg)
        else:
            d["mamba"] = mamba_decl(cfg)
        if cfg.post_block_norms:
            d["ln1p"] = ParamDecl((cfg.d_model,), ("embed",), init="ones")
        if cross and self.kinds[i] == "attn":
            d["ln_x"] = ParamDecl((cfg.d_model,), ("embed",), init="ones")
            d["cross"] = attn_decl(cfg, cross=True)
        if self.has_ffn:
            d["ln2"] = ParamDecl((cfg.d_model,), ("embed",), init="ones")
            if self.ffns[i] == "moe":
                d["moe"] = moe_decl(cfg)
            else:
                d["mlp"] = mlp_decl(cfg)
            if cfg.post_block_norms:
                d["ln2p"] = ParamDecl((cfg.d_model,), ("embed",), init="ones")
        return d

    def decls(self) -> dict:
        cfg = self.cfg
        per = {
            f"sub{i}": self._sub_decl(i, cross=cfg.is_encoder_decoder)
            for i in range(self.period)
        }
        tree = {
            "embed": ParamDecl(
                (cfg.vocab_size, cfg.d_model), ("vocab", "fsdp"), fan_in=cfg.d_model
            ),
            "blocks": stacked(per, self.n_super),
            "final_norm": ParamDecl((cfg.d_model,), ("embed",), init="ones"),
        }
        if not cfg.tie_embeddings:
            tree["lm_head"] = ParamDecl(
                (cfg.d_model, cfg.vocab_size), ("fsdp", "vocab"), fan_in=cfg.d_model
            )
        if cfg.is_encoder_decoder:
            enc_sub = {
                "ln1": ParamDecl((cfg.d_model,), ("embed",), init="ones"),
                "attn": attn_decl(cfg),
                "ln2": ParamDecl((cfg.d_model,), ("embed",), init="ones"),
                "mlp": mlp_decl(cfg),
            }
            tree["enc_blocks"] = stacked({"sub0": enc_sub}, cfg.num_encoder_layers)
            tree["enc_final_norm"] = ParamDecl((cfg.d_model,), ("embed",), init="ones")
        return tree

    def init(self, key: jax.Array, dtype=F32) -> dict:
        return init_tree(key, self.decls(), dtype)

    def param_axes(self) -> dict:
        return axes_tree(self.decls())

    def param_shapes(self, dtype=F32) -> dict:
        return shape_tree(self.decls(), dtype)

    # ------------------------------------------------------------------
    # Sublayer body
    # ------------------------------------------------------------------
    def _sub_apply(
        self,
        p: dict,
        i: int,
        x: jax.Array,
        *,
        positions: jax.Array,
        cache: Optional[dict],
        lengths: Optional[jax.Array],
        want_cache: bool,
        enc_out: Optional[jax.Array],
        cross_kv: Optional[dict],
    ):
        cfg = self.cfg
        h = rms_norm(p["ln1"], x, cfg.norm_eps)
        new_cache: dict = {}
        if self.kinds[i] == "attn":
            if cache is not None:
                c_in = cache["attn"]
            elif want_cache:
                c_in = {}
            else:
                c_in = None
            mix, nc = attention(
                p["attn"],
                h,
                cfg=cfg,
                positions=positions,
                window=self.windows[i],
                cache=c_in,
                lengths=lengths,
                impl=self.impl,
                kv_quant=self.kv_quant,
            )
            if nc is not None:
                new_cache["attn"] = nc
        else:
            c_in = cache["mamba"] if cache is not None else None
            mix, nc = mamba_apply(
                p["mamba"], h, cfg=cfg, cache=c_in, want_cache=want_cache, impl=self.impl
            )
            if nc is not None:
                new_cache["mamba"] = nc
        if cfg.post_block_norms:
            mix = rms_norm(p["ln1p"], mix, cfg.norm_eps)
        x = x + mix

        if "cross" in p and (enc_out is not None or cross_kv is not None):
            h = rms_norm(p["ln_x"], x, cfg.norm_eps)
            if cross_kv is not None:
                kv = (cross_kv["k"], cross_kv["v"], cross_kv["pos_ids"])
            else:
                dt = h.dtype
                ek = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wk"].astype(dt))
                ev = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wv"].astype(dt))
                epos = jnp.broadcast_to(
                    jnp.arange(enc_out.shape[1], dtype=jnp.int32)[None],
                    enc_out.shape[:2],
                )
                if want_cache:
                    new_cache["cross"] = {"k": ek, "v": ev, "pos_ids": epos}
                kv = (ek, ev, epos)
            cx, _ = attention(
                p["cross"],
                h,
                cfg=cfg,
                positions=positions,
                kv_override=kv,
                causal=False,
                use_rope=False,
                impl=self.impl,
            )
            x = x + cx

        aux = jnp.zeros((), F32)
        if self.has_ffn:
            h = rms_norm(p["ln2"], x, cfg.norm_eps)
            if self.ffns[i] == "moe":
                f, aux = moe_apply(p["moe"], h, cfg)
            else:
                f = mlp_apply(p["mlp"], h, cfg)
            if cfg.post_block_norms:
                f = rms_norm(p["ln2p"], f, cfg.norm_eps)
            x = x + f
        return x, new_cache, aux

    # ------------------------------------------------------------------
    # Layer scan
    # ------------------------------------------------------------------
    def _scan_blocks(
        self,
        params: dict,
        x: jax.Array,
        *,
        positions: jax.Array,
        cache: Optional[dict] = None,  # stacked self caches (decode)
        cross: Optional[dict] = None,  # stacked cross-KV (decode, read-only)
        lengths: Optional[jax.Array] = None,
        want_cache: bool = False,
        enc_out: Optional[jax.Array] = None,
        remat: Optional[str] = None,
    ):
        has_cache, has_cross = cache is not None, cross is not None

        def body(carry, xs):
            xc = carry
            p_super, cache_s, cross_s = xs
            caches, auxes = {}, []
            for i in range(self.period):
                sub_cache = cache_s.get(f"sub{i}") if has_cache else None
                sub_cross = cross_s.get(f"sub{i}") if has_cross else None
                xc, nc, aux = self._sub_apply(
                    p_super[f"sub{i}"],
                    i,
                    xc,
                    positions=positions,
                    cache=sub_cache,
                    lengths=lengths,
                    want_cache=want_cache,
                    enc_out=enc_out,
                    cross_kv=sub_cross,
                )
                caches[f"sub{i}"] = nc
                auxes.append(aux)
            return xc, (caches, sum(auxes))

        if remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        elif remat == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                prevent_cse=False,
            )
        elif remat == "coll":
            # save only the all-reduced sublayer outputs: backward never
            # re-runs forward collectives, residual memory stays ~(B,S,D)
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_only_these_names("coll_out"),
                prevent_cse=False,
            )
        xs = (params["blocks"], cache if has_cache else {}, cross if has_cross else {})
        x, (new_caches, auxes) = jax.lax.scan(body, x, xs, unroll=self.scan_unroll)
        return x, new_caches, jnp.sum(auxes)

    # ------------------------------------------------------------------
    # Embedding / head / encoder
    # ------------------------------------------------------------------
    def embed(self, params, tokens, frontend_embeds=None, dtype=jnp.bfloat16):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
        if frontend_embeds is not None:
            x = jnp.concatenate([frontend_embeds.astype(dtype), x], axis=1)
        return shard(x, "batch", "seq", "embed")

    def head(self, params, x) -> jax.Array:
        cfg = self.cfg
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum(
            "bsd,dv->bsv", x, w.astype(x.dtype), preferred_element_type=F32
        )
        logits = softcap(logits, cfg.final_logit_softcap)
        return shard(logits, "batch", "seq", "vocab")

    def encode(self, params, enc_embeds, remat=None):
        """Encoder stack over precomputed frame embeddings (audio stub)."""
        cfg = self.cfg
        x = shard(enc_embeds, "batch", "seq", "embed")
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2]
        )

        def body(carry, p_super):
            h = carry
            p = p_super["sub0"]
            a = rms_norm(p["ln1"], h, cfg.norm_eps)
            mix, _ = attention(
                p["attn"], a, cfg=cfg, positions=positions, causal=False, impl=self.impl
            )
            h = h + mix
            f = mlp_apply(p["mlp"], rms_norm(p["ln2"], h, cfg.norm_eps), cfg)
            return h + f, None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, params["enc_blocks"], unroll=self.scan_unroll)
        return rms_norm(params["enc_final_norm"], x, cfg.norm_eps)

    # ------------------------------------------------------------------
    # Public steps
    # ------------------------------------------------------------------
    def forward(self, params, tokens, *, frontend_embeds=None, enc_embeds=None,
                remat=None, dtype=jnp.bfloat16):
        """Teacher-forced forward; returns (logits, moe_aux)."""
        x, aux = self.hidden(
            params, tokens, frontend_embeds=frontend_embeds,
            enc_embeds=enc_embeds, remat=remat, dtype=dtype,
        )
        return self.head(params, x), aux

    def hidden(self, params, tokens, *, frontend_embeds=None, enc_embeds=None,
               remat=None, dtype=jnp.bfloat16):
        """Embed -> blocks -> final norm; returns (x, moe_aux)."""
        cfg = self.cfg
        x = self.embed(params, tokens, frontend_embeds, dtype)
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2]
        )
        enc_out = None
        if cfg.is_encoder_decoder:
            assert enc_embeds is not None, "enc-dec model requires enc_embeds"
            enc_out = self.encode(params, enc_embeds.astype(dtype), remat=remat)
        x, _, aux = self._scan_blocks(
            params, x, positions=positions, enc_out=enc_out, remat=remat
        )
        return rms_norm(params["final_norm"], x, cfg.norm_eps), aux

    def loss(self, params, batch, *, remat=None, dtype=jnp.bfloat16):
        """batch: tokens (B,S), targets (B,S) [+ patch_embeds / enc_embeds]."""
        cfg = self.cfg
        x, aux = self.hidden(
            params,
            batch["tokens"],
            frontend_embeds=batch.get("patch_embeds"),
            enc_embeds=batch.get("enc_embeds"),
            remat=remat,
            dtype=dtype,
        )
        if cfg.frontend == "vision_patches" and cfg.frontend_tokens:
            x = x[:, cfg.frontend_tokens :, :]
        ce = chunked_ce(lambda xc: self.head(params, xc), x, batch["targets"])
        total = ce + cfg.router_aux_weight * aux
        return total, {"ce": ce, "aux": aux}

    # --- serving ---
    def _attn_cache_len(self, kv_len: int, window: Optional[int]) -> int:
        if window and 0 < window <= kv_len:
            return window  # ring buffer
        return kv_len + 128  # headroom so full-attn decode never wraps

    def cache_spec(self, batch: int, kv_len: int, dtype=jnp.bfloat16,
                   enc_len: Optional[int] = None) -> dict:
        """ShapeDtypeStructs for a decode-ready cache at context kv_len."""
        cfg = self.cfg
        K, hd = cfg.num_kv_heads, cfg.head_dim
        per, per_cross = {}, {}
        for i in range(self.period):
            sub = {}
            if self.kinds[i] == "attn":
                smax = self._attn_cache_len(kv_len, self.windows[i])
                if self.kv_quant:
                    sub["attn"] = {
                        "k_q": jax.ShapeDtypeStruct((batch, smax, K, hd), jnp.int8),
                        "v_q": jax.ShapeDtypeStruct((batch, smax, K, hd), jnp.int8),
                        "k_s": jax.ShapeDtypeStruct((batch, smax, K), F32),
                        "v_s": jax.ShapeDtypeStruct((batch, smax, K), F32),
                        "pos_ids": jax.ShapeDtypeStruct((batch, smax), jnp.int32),
                    }
                else:
                    sub["attn"] = {
                        "k": jax.ShapeDtypeStruct((batch, smax, K, hd), dtype),
                        "v": jax.ShapeDtypeStruct((batch, smax, K, hd), dtype),
                        "pos_ids": jax.ShapeDtypeStruct((batch, smax), jnp.int32),
                    }
                if cfg.is_encoder_decoder:
                    senc = enc_len or kv_len
                    per_cross[f"sub{i}"] = {
                        "k": jax.ShapeDtypeStruct((batch, senc, K, hd), dtype),
                        "v": jax.ShapeDtypeStruct((batch, senc, K, hd), dtype),
                        "pos_ids": jax.ShapeDtypeStruct((batch, senc), jnp.int32),
                    }
            else:
                sub["mamba"] = mamba_cache_decl(cfg, batch, dtype)
            per[f"sub{i}"] = sub

        def stack(sd):
            return jax.ShapeDtypeStruct((self.n_super,) + sd.shape, sd.dtype)

        out = {
            "lengths": jax.ShapeDtypeStruct((batch,), jnp.int32),
            "blocks": jax.tree.map(stack, per),
        }
        if cfg.is_encoder_decoder:
            out["cross"] = jax.tree.map(stack, per_cross)
        return out

    def cache_axes(self, cache_spec: dict) -> dict:
        """Logical sharding axes for every cache leaf (by leaf name)."""

        def one(path, leaf):
            names = [getattr(k, "key", str(k)) for k in path]
            stacked_ = "blocks" in names or "cross" in names
            lead = ("layers",) if stacked_ else ()
            name = names[-1]
            if name == "lengths":
                return ("batch",)
            if name in ("k", "v", "k_q", "v_q"):
                return lead + ("batch", "kv_seq", "kv_heads", "head_dim")
            if name in ("k_s", "v_s"):
                return lead + ("batch", "kv_seq", "kv_heads")
            if name == "pos_ids":
                return lead + ("batch", "kv_seq")
            if name == "ssm":
                return lead + ("batch", "ssm_heads", None, None)
            if name == "conv":
                return lead + ("batch", None, "conv_ch")
            raise ValueError(f"unknown cache leaf {names}")

        return jax.tree_util.tree_map_with_path(one, cache_spec)

    def init_cache(self, batch: int, kv_len: int, dtype=jnp.bfloat16,
                   enc_len: Optional[int] = None) -> dict:
        spec = self.cache_spec(batch, kv_len, dtype, enc_len)

        def zero(sd):
            if sd.dtype == jnp.int32:
                return jnp.full(sd.shape, -1, jnp.int32)
            return jnp.zeros(sd.shape, sd.dtype)

        cache = jax.tree.map(zero, spec)
        cache["lengths"] = jnp.zeros((batch,), jnp.int32)
        return cache

    def prefill(self, params, tokens, *, kv_len: Optional[int] = None,
                frontend_embeds=None, enc_embeds=None, dtype=jnp.bfloat16):
        """Process a full prompt; returns (last_logits, decode-ready cache)."""
        cfg = self.cfg
        x = self.embed(params, tokens, frontend_embeds, dtype)
        B, S = x.shape[:2]
        kv_len = kv_len or S
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self.encode(params, enc_embeds.astype(dtype))
        x, caches, _ = self._scan_blocks(
            params, x, positions=positions, want_cache=True, enc_out=enc_out
        )
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self.head(params, x[:, -1:, :])[:, 0]
        cache = self._finalize_prefill_cache(caches, B, S, kv_len)
        return logits, cache

    def _finalize_prefill_cache(self, caches, B, S, kv_len):
        """Pad/ring-place prefill K/V into the decode-cache layout."""

        def place(path, leaf):
            names = [getattr(k, "key", str(k)) for k in path]
            if "mamba" in names or "cross" in names:
                return leaf
            sub_i = int([n for n in names if n.startswith("sub")][0][3:])
            smax = self._attn_cache_len(kv_len, self.windows[sub_i])
            is_pos = names[-1] == "pos_ids"
            # leaf: (n_super, B, S, ...)
            if smax >= S:
                pad = [(0, 0)] * leaf.ndim
                pad[2] = (0, smax - S)
                return jnp.pad(leaf, pad, constant_values=-1 if is_pos else 0)
            # ring: contiguous prefill keeps the last smax positions at
            # slots p % smax
            idx = jnp.arange(S - smax, S) % smax
            kept = leaf[:, :, S - smax :]
            out = jnp.full(
                leaf.shape[:2] + (smax,) + leaf.shape[3:],
                -1 if is_pos else 0,
                leaf.dtype,
            )
            return out.at[:, :, idx].set(kept)

        blocks = jax.tree_util.tree_map_with_path(place, caches)
        out = {"lengths": jnp.full((B,), S, jnp.int32), "blocks": blocks}
        if self.cfg.is_encoder_decoder:
            cross = {}
            for sk, sub in blocks.items():
                if "cross" in sub:
                    cross[sk] = sub.pop("cross")
            out["cross"] = cross
        return out

    def decode_step(self, params, cache, tokens, dtype=jnp.bfloat16):
        """One decode step for every sequence. tokens: (B, S_new).

        Returns (logits (B, V) for the last position, new cache)."""
        cfg = self.cfg
        lengths = cache["lengths"]
        x = self.embed(params, tokens, None, dtype)
        positions = lengths[:, None] + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
        x, new_blocks, _ = self._scan_blocks(
            params,
            x,
            positions=positions,
            cache=cache["blocks"],
            cross=cache.get("cross"),
            lengths=lengths,
            want_cache=False,
        )
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self.head(params, x)[:, -1]
        new_cache = {"lengths": lengths + tokens.shape[1], "blocks": new_blocks}
        if "cross" in cache:
            new_cache["cross"] = cache["cross"]
        return logits, new_cache
