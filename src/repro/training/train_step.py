"""Training step: loss -> grads -> AdamW, with microbatched grad accumulation.

Microbatching is a lax.scan over microbatch slices; the gradient
reduce(-scatter) of microbatch m overlaps the compute of m+1 exactly like
the iFDK projection pipeline (DESIGN.md §5: the same gather-compute-reduce
schedule drives both the CT reconstruction and training).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ModelConfig
from repro.models.transformer import (
    abstract_params, init_params, loss_fn, param_shardings,
)
from repro.optim import AdamWConfig, OptState, adamw_init, adamw_update
from repro.optim.schedule import cosine_schedule
from repro.parallel.sharding import ShardingRules

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree
    opt: OptState


def init_train_state(cfg: ModelConfig, key) -> TrainState:
    params = init_params(cfg, key)
    return TrainState(params=params, opt=adamw_init(params))


def make_abstract_state(cfg: ModelConfig) -> TrainState:
    params = abstract_params(cfg)
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)  # noqa: E731
    return TrainState(
        params=params,
        opt=OptState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=jax.tree.map(f32, params),
            nu=jax.tree.map(f32, params),
        ),
    )


def state_shardings(cfg: ModelConfig, rules: ShardingRules) -> TrainState:
    ps = param_shardings(cfg, rules)
    return TrainState(
        params=ps,
        opt=OptState(
            step=rules.sharding() if rules.mesh is not None else None,
            mu=ps, nu=ps,
        ),
    )


def make_train_step(cfg: ModelConfig,
                    opt_cfg: AdamWConfig = AdamWConfig(),
                    rules: Optional[ShardingRules] = None,
                    microbatches: int = 1,
                    warmup: int = 100, total_steps: int = 10_000,
                    remat: bool = True):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, rules, remat)[0]
        )(params)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        params = state.params
        if microbatches == 1:
            loss, grads = grads_of(params, batch)
        else:
            def slice_mb(x):
                b = x.shape[0]
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])
            mbs = jax.tree.map(slice_mb, batch)

            def mb_step(acc, mb):
                loss_acc, grad_acc = acc
                loss, grads = grads_of(params, mb)
                grad_acc = jax.tree.map(jnp.add, grad_acc, grads)
                return (loss_acc + loss, grad_acc), None

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss, grads), _ = lax.scan(
                mb_step, (jnp.zeros((), jnp.float32), zero_g), mbs
            )
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)

        lr_scale = cosine_schedule(state.opt.step + 1, warmup, total_steps)
        new_params, new_opt, om = adamw_update(
            opt_cfg, grads, state.opt, params, lr_scale
        )
        metrics = {"loss": loss, "grad_norm": om["grad_norm"],
                   "lr_scale": lr_scale}
        return TrainState(new_params, new_opt), metrics

    return train_step
