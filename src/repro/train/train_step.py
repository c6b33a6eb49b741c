"""Train-step factory: loss + grad (+ microbatch accumulation) + AdamW.

The returned step is a single jit-able function of (params, opt_state,
batch) suitable for pjit with the shardings from models/sharding.py; ZeRO
falls out of the param/opt shardings, remat from models.forward, and
compute/comm overlap from XLA's scheduling of the scan's all-gathers.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models import sharding as shd
from repro.models.config import ModelConfig
from . import optimizer as opt

F32 = jnp.float32


def make_loss_fn(cfg: ModelConfig) -> Callable:
    def loss_fn(params, batch):
        logits, _, aux = M.forward(cfg, params, batch)
        return M.loss_fn(cfg, logits, batch, aux)
    return loss_fn


def make_train_step(cfg: ModelConfig, ocfg: opt.AdamWConfig,
                    grad_accum: int = 1,
                    accum_dtype=jnp.float32) -> Callable:
    """grad_accum > 1 scans over microbatches (slices of the leading batch
    dim) — the production config for the large archs, bounding the remat
    residual stack to one microbatch.  ``accum_dtype=bfloat16`` halves the
    accumulator for trillion-param state budgets (kimi)."""
    loss_fn = make_loss_fn(cfg)

    def step(params, opt_state, batch):
        params, opt_state, batch = shd.auto_typed((params, opt_state, batch))
        if grad_accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            def micro(carry, mb):
                acc_loss, acc_g = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (acc_loss + l,
                        jax.tree.map(lambda a, b: a + b.astype(accum_dtype),
                                     acc_g, g)), ()

            mbs = jax.tree.map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                    *x.shape[1:]), batch)
            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)
            (loss, grads), _ = jax.lax.scan(micro, (0.0, zero_g), mbs)
            loss = loss / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
        params, opt_state, metrics = opt.apply_updates(ocfg, params, grads,
                                                       opt_state)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return step


def make_eval_step(cfg: ModelConfig) -> Callable:
    loss_fn = make_loss_fn(cfg)

    def step(params, batch):
        return loss_fn(params, batch)

    return step
