"""Train-step builders.

``make_train_step``          standard pjit SPMD step: value_and_grad (with
                             optional microbatch gradient accumulation) +
                             AdamW.  XLA inserts the DP gradient
                             all-reduces / FSDP all-gathers from the param
                             shardings.

``make_robust_train_step``   DCF-PCA aggregation path: per-worker gradients
                             are exposed by a shard_map over the DP axes
                             (the model axis stays in auto/pjit mode), then
                             every large 2-D gradient is aggregated by
                             consensus factorization instead of plain
                             all-reduce (repro.distributed.grad_compress).
                             Requires params not FSDP-sharded over DP.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import grad_compress as gc
from repro.distributed.sharding import ShardingRules
from repro.models import Model
from repro.training import optimizer as opt

Array = jax.Array


def make_train_step(
    model: Model,
    opt_cfg: opt.AdamWConfig,
    rules: ShardingRules,
    *,
    microbatches: int = 1,
) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, state, metrics)."""

    def loss_fn(params, batch):
        loss, mets = model.loss(params, batch, rules)
        return loss, mets

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single(params, batch):
        (loss, mets), grads = grad_fn(params, batch)
        return loss, mets, grads

    def accumulated(params, batch):
        # Split the global batch into microbatches along dim 0 and scan,
        # averaging gradients -- cuts activation memory by ~microbatches x.
        def micro(b):
            return jax.tree.map(
                lambda x: x.reshape(microbatches, -1, *x.shape[1:]), b)

        def body(acc, mb):
            (loss, mets), grads = grad_fn(params, mb)
            acc_g, acc_l = acc
            acc_g = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / microbatches,
                acc_g, grads)
            return (acc_g, acc_l + loss / microbatches), mets

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss), mets = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)), micro(batch))
        mets = jax.tree.map(lambda x: x[-1], mets)
        return loss, mets, grads

    fwd_bwd = single if microbatches == 1 else accumulated

    def train_step(params, opt_state, batch):
        loss, mets, grads = fwd_bwd(params, batch)
        params, opt_state, om = opt.update(opt_cfg, grads, opt_state, params)
        return params, opt_state, {"loss": loss, **mets, **om}

    return train_step


def make_robust_train_step(
    model: Model,
    opt_cfg: opt.AdamWConfig,
    mesh: Mesh,
    rules: ShardingRules,
    ccfg: gc.CompressConfig,
) -> Callable:
    """DCF-PCA consensus gradient aggregation across the DP axes."""
    dp_axes = rules.dp
    if dp_axes is None:
        raise ValueError("robust aggregation needs a DP mesh axis")
    dp_axes = tuple(dp_axes) if isinstance(dp_axes, (tuple, list)) else (dp_axes,)
    # Inside the shard_map the batch is local: dp resolves to None; the
    # model (tp/sp) axes stay in auto mode and keep their pjit meaning
    # (jax.shard_map's axis_names lists only the MANUAL axes).
    inner_rules = dataclasses.replace(rules, dp=None, fsdp=None)

    def loss_fn(params, batch):
        loss, mets = model.loss(params, batch, inner_rules)
        return loss, mets

    def per_worker(params, batch, key):
        (loss, mets), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        grads = gc.aggregate_tree(grads, dp_axes, ccfg, key)
        loss = jax.lax.pmean(loss, dp_axes)
        mets = jax.tree.map(lambda x: jax.lax.pmean(x, dp_axes), mets)
        return grads, loss, mets

    def train_step(params, opt_state, batch, key):
        batch_specs = jax.tree.map(
            lambda x: P(dp_axes, *(None,) * (x.ndim - 1)), batch)
        param_specs = jax.tree.map(lambda _: P(), params)
        grads, loss, mets = jax.shard_map(
            per_worker,
            mesh=mesh,
            in_specs=(param_specs, batch_specs, P()),
            out_specs=(param_specs, P(), P()),
            axis_names=frozenset(dp_axes),
            check_vma=False,
        )(params, batch, key)
        params, opt_state, om = opt.update(opt_cfg, grads, opt_state, params)
        return params, opt_state, {"loss": loss, **mets, **om}

    return train_step
