"""Jitted public wrappers around the Pallas kernels.

* On TPU the kernels run compiled (interpret=False); on any other
  backend they run in interpret mode — same kernel body, evaluated by
  XLA — which is how the CPU tests validate them. The choice is made
  when a wrapper is traced (``_interpret``), never at import: importing
  this module touches no JAX backend, so it cannot claim the chip.
* ``sdpa_flash`` registers itself as the "pallas" SDPA implementation in
  models/layers.py, so any model can switch its attention inner loop to
  the kernel with ``LM(cfg, impl="pallas")``; building such a model
  imports this module.
* Training differentiability: flash_attention gets a custom_vjp whose
  backward rematerializes through the jnp oracle (exact same math). The
  dedicated TPU backward kernel is future work; serving (the paper's
  workload) only needs forward.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..models import layers as _layers
from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .ref import decode_attention_ref, flash_attention_ref, ssd_scan_ref
from .ssd_scan import ssd_scan


def _interpret() -> bool:
    """Interpret mode unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_diff(q, k, v, causal=True, window=0, softcap=0.0):
    return flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        interpret=_interpret(),
    )


def _fa_fwd(q, k, v, causal, window, softcap):
    out = flash_attention_diff(q, k, v, causal, window, softcap)
    return out, (q, k, v)


def _fa_bwd(causal, window, softcap, res, g):
    q, k, v = res

    def ref(q, k, v):
        return flash_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap
        )

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


flash_attention_diff.defvjp(_fa_fwd, _fa_bwd)


def sdpa_flash(q, k, v, q_pos, k_pos, window, causal, cap):
    """models/layers.py SDPA_IMPL["pallas"] adapter.

    Contiguous-position fast paths use the kernels; ragged cases (ring
    caches mid-wrap, cross-attention against cached positions) fall back
    to the oracle.
    """
    B, Sq, H, hd = q.shape
    win = int(window) if isinstance(window, int) and window else 0
    capf = float(cap) if cap else 0.0
    if Sq == 1 and k.shape[1] % 128 == 0:
        lengths = q_pos[:, 0]
        return decode_attention(
            q[:, 0], k, v, k_pos, lengths,
            window=win, softcap=capf, interpret=_interpret(),
        )[:, None]
    if Sq % 128 == 0 and k.shape[1] % 128 == 0 and Sq == k.shape[1]:
        return flash_attention_diff(q, k, v, causal, win, capf)
    return _layers._sdpa_jnp(q, k, v, q_pos, k_pos, window, causal, cap)


_layers.SDPA_IMPL["pallas"] = sdpa_flash

__all__ = [
    "flash_attention",
    "flash_attention_diff",
    "decode_attention",
    "ssd_scan",
    "sdpa_flash",
    "flash_attention_ref",
    "decode_attention_ref",
    "ssd_scan_ref",
]
