"""Manual-SPMD data-parallel train step with int8 gradient compression.

Under plain pjit, the gradient all-reduce is fused into the backward pass
and is not interceptable. This variant takes manual control with shard_map
over the data axes: per-device gradients are synchronized with
``compressed_psum`` (int8 codes + one scale per leaf — 4x fewer bytes on
the wire than f32, unbiased via stochastic rounding), then the AdamW update
runs replicated. This is the distributed-optimization pattern for
DCN-limited multi-pod gradient sync (the `pod` axis in the production mesh
is data-center network, ~10x slower than ICI — compressing the cross-pod
reduce is where this pays).

Correctness: tests/test_distributed.py compares loss trajectories against
the exact-psum step.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.model import loss_fn
from repro.training.compression import compressed_psum
from repro.training.optimizer import OptimizerConfig, adamw_update
from repro.training.train_loop import TrainState

PyTree = Any


def make_dp_train_step_compressed(cfg: ModelConfig, opt: OptimizerConfig,
                                  mesh: Mesh, *, compress: bool = True):
    """Build a shard_map DP train step.

    Params/optimizer state replicated; batch sharded over the data axes.
    Returns fn(state, batch, key) -> (state, metrics). `compress=False`
    gives the exact-psum twin (for A/B tests).
    """
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not data_axes:
        raise ValueError("mesh has no data axes")
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]

    def local_step(state: TrainState, batch: dict, key):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, cfg, batch)
        # synchronize gradients across the data axes
        for ax in data_axes:
            if compress:
                grads = compressed_psum(grads, ax, key[0])
            else:
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.psum(g, ax), grads)
        grads = jax.tree_util.tree_map(lambda g: g / n_shards, grads)
        loss = jax.lax.pmean(loss, data_axes[0])
        for ax in data_axes[1:]:
            loss = jax.lax.pmean(loss, ax)
        params, opt_state, opt_metrics = adamw_update(
            opt, grads, state.opt_state, state.params)
        out_metrics = {"loss": loss, **opt_metrics}
        return TrainState(params=params, opt_state=opt_state), out_metrics

    batch_spec = P(data_axes)
    fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), {"tokens": batch_spec, "labels": batch_spec},
                  P(data_axes)),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))
