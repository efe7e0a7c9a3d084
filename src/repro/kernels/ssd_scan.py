"""Mamba2 SSD chunked scan for TPU.

Grid = (batch, head, chunk) with the chunk axis innermost: the running
inter-chunk state (P x N, f32) lives in VMEM scratch and is carried
across sequential grid steps — the TPU-native replacement for the GPU
kernel's warp-level chunk pipeline. Per chunk the intra-chunk quadratic
term is two (Q,N)x(N,Q) / (Q,Q)x(Q,P) MXU matmuls; Q=128 keeps every
matmul dim hardware-aligned.

Layouts (head-major so one program owns one head's sequence):
  x   (B, H, nc, Q, P)   cs (B, H, nc, 1, Q) and (B, H, nc, Q, 1)
  B_*dt (B, H, nc, Q, N) f32   C_ (B, H, nc, Q, N)
where cs is the chunk-inclusive cumsum of dt*A. Every block's last two
dims are either full or (8,128)-aligned, so any number of chunks tiles.
Outputs: y (B, H, nc, Q, P), final state (B, H, P, N) f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _ssd_kernel(
    x_ref,  # (1, 1, 1, Q, P)
    csr_ref,  # (1, 1, 1, 1, Q) chunk-inclusive cumsum of dt*A, as a row
    csc_ref,  # (1, 1, 1, Q, 1) the same, as a column
    b_ref,  # (1, 1, 1, Q, N) f32, dt folded in
    c_ref,  # (1, 1, 1, Q, N)
    y_ref,  # (1, 1, 1, Q, P)
    fs_ref,  # (1, 1, P, N) final state
    state,  # scratch (P, N) f32
    *,
    num_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, 0, 0].astype(F32)  # (Q, P)
    cs_r = csr_ref[0, 0, 0]  # (1, Q)
    cs_c = csc_ref[0, 0, 0]  # (Q, 1)
    Bd = b_ref[0, 0, 0]  # (Q, N) = B * dt
    C_ = c_ref[0, 0, 0].astype(F32)  # (Q, N)
    Q = x.shape[0]

    # intra-chunk: y_q = sum_{k<=q} (C_q . B_k) dt_k exp(cs_q - cs_k) x_k
    scores = jax.lax.dot_general(
        C_, Bd, (((1,), (1,)), ((), ())), preferred_element_type=F32
    )  # (Q, Q)
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    )
    L = jnp.where(tri, jnp.exp(cs_c - cs_r), 0.0)
    y = jax.lax.dot_general(
        scores * L, x, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )  # (Q, P)
    # inter-chunk: y += (C * exp(cs)) @ state^T
    Cw = C_ * jnp.exp(cs_c)
    y += jax.lax.dot_general(
        Cw, state[...], (((1,), (1,)), ((), ())), preferred_element_type=F32
    )
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update: state = exp(cs_last) * state + x^T @ (B dt exp(cs_last - cs))
    cs_last = cs_r[:, Q - 1:]  # (1, 1)
    upd = jax.lax.dot_general(
        x, Bd * jnp.exp(cs_last - cs_c), (((0,), (0,)), ((), ())),
        preferred_element_type=F32,
    )  # (P, N)
    state[...] = jnp.exp(cs_last) * state[...] + upd

    @pl.when(ic == num_chunks - 1)
    def _final():
        fs_ref[0, 0] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) softplus'ed
    A: jax.Array,  # (H,) negative
    B_: jax.Array,  # (B, S, H, N)
    C_: jax.Array,  # (B, S, H, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    B, S, H, P = x.shape
    N = B_.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc, Q = S // chunk, chunk

    def head_major(t):  # (B,S,H,...) -> (B,H,nc,Q,...)
        t = jnp.moveaxis(t, 2, 1)  # (B,H,S,...)
        return t.reshape(t.shape[:2] + (nc, Q) + t.shape[3:])

    xr = head_major(x)
    dtr = head_major(dt[..., None])[..., 0]  # (B,H,nc,Q)
    # the chunk cumsum runs here: Mosaic lowers no in-kernel cumsum, and
    # no (1,Q) -> (Q,1) relayout, so the kernel gets both orientations
    cs = jnp.cumsum(dtr * A[None, :, None, None].astype(F32), axis=-1)
    cs_row, cs_col = cs[..., None, :], cs[..., :, None]
    Bd = head_major(B_.astype(F32) * dt[..., None].astype(F32))
    Cr = head_major(C_)

    kernel = functools.partial(_ssd_kernel, num_chunks=nc)
    y, fs = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), F32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), F32)],
        interpret=interpret,
    )(xr, cs_row, cs_col, Bd, Cr)
    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)  # (B,S,H,P)
    return y, fs
