"""Data-parallel training with int8 error-feedback gradient reduction.

shard_map over the "data" axis: params replicated, batch sharded, each
worker computes local grads, the cross-worker mean is transmitted int8
(parallel/compress.py). Used (a) as a distributed-optimization option in
the training driver, (b) as the §Perf "compressed-DP" dry-run variant
whose compiled HLO shows s8 all-gathers replacing f32 all-reduces.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.transformer import LM
from ..optim import adamw

F32 = jnp.float32


def init_state(model: LM, key) -> dict:
    params = model.init(key, dtype=F32)
    return {
        "params": params,
        "opt": adamw.init(params),
        "err": jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params),
        "step": jnp.zeros((), jnp.int32),
    }


def make_dp_train_step(
    model: LM,
    opt_cfg: adamw.OptConfig,
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "data",
    compress: bool = True,
    remat: Optional[str] = None,
):
    """Returns (state, batch) -> (state, metrics); batch sharded on `axis`."""
    from ..parallel.compress import tree_ef_allreduce_mean

    def local_loss(params, batch):
        loss, _ = model.loss(params, batch, remat=remat)
        return loss

    def shard_body(state, batch):
        params = state["params"]
        loss, grads = jax.value_and_grad(local_loss)(params, batch)
        loss = jax.lax.pmean(loss, axis)
        if compress:
            grads, new_err = tree_ef_allreduce_mean(grads, state["err"], axis)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
            new_err = state["err"]
        new_params, new_opt, om = adamw.update(
            opt_cfg, params, grads, state["opt"], state["step"]
        )
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "err": new_err,
            "step": state["step"] + 1,
        }
        return new_state, {"loss": loss, **om}

    def step(state, batch):
        rep = P()
        bspec = P(axis)
        in_specs = (
            jax.tree.map(lambda _: rep, state),
            jax.tree.map(lambda _: bspec, batch),
        )
        out_specs = (
            jax.tree.map(lambda _: rep, state),
            {"loss": rep, "grad_norm": rep, "lr": rep},
        )
        sm = jax.shard_map(
            shard_body, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        )
        return sm(state, batch)

    return step
