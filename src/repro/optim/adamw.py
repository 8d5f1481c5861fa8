"""AdamW with global-norm clipping, cosine schedule and ZeRO-1 state sharding.

Built from scratch (no optax in this environment).  The optimizer state can be
sharded over the ``data`` axis (ZeRO-1): ``zero1_specs`` rewrites each state
leaf's PartitionSpec to add the data axis on the first evenly-divisible
unsharded dim, so m/v never cost more than params/dp per device.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


class AdamWState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any
    master: Any        # f32 master weights (model params stay bf16)


def init(params) -> AdamWState:
    # zeros_like keeps each leaf's sharding: the moments live where
    # their params do
    f32 = lambda t: jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), t)
    master = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    return AdamWState(jnp.zeros((), jnp.int32), f32(params), f32(params), master)


def schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip((step - cfg.warmup_steps)
                 / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
    return cfg.lr * warm * (cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def update(grads, state: AdamWState, params, cfg: AdamWConfig):
    """Mixed precision: bf16 grads update the f32 master; model params are the
    bf16 cast of the master.  Returns (new_params, new_state, metrics)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    step = state.step + 1
    lr = schedule(cfg, step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    def leaf(g, m, v, w, p):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        upd = (m / b1c) / (jnp.sqrt(v / b2c) + cfg.eps) + cfg.weight_decay * w
        w = w - lr * upd
        return (w.astype(p.dtype), m, v, w)

    out = jax.tree.map(leaf, grads, state.mu, state.nu, state.master, params)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), AdamWState(step, pick(1), pick(2), pick(3)), {
        "grad_norm": gnorm, "lr": lr}


def zero1_specs(param_specs, params_shapes, data_size: int):
    """ZeRO-1: add the data axis to the first unsharded, divisible dim of each
    m/v leaf spec.  Falls back to the param spec when no dim qualifies."""
    def respec(spec: P, leaf) -> P:
        flat_axes = [a for d in spec if d for a in (d if isinstance(d, tuple) else (d,))]
        if "data" in flat_axes:
            return spec                      # already data-sharded (FSDP leaf)
        dims = list(spec) + [None] * (leaf.ndim - len(spec))
        for i, (d, s) in enumerate(zip(leaf.shape, dims)):
            if s is None and d % data_size == 0 and d >= data_size:
                dims[i] = "data"
                return P(*dims)
        return spec

    return jax.tree.map(respec, param_specs, params_shapes,
                        is_leaf=lambda x: isinstance(x, P))


def state_specs(param_specs, params_shapes, data_size: int, zero1: bool = True):
    mv = zero1_specs(param_specs, params_shapes, data_size) if zero1 else param_specs
    return AdamWState(P(), mv, mv, mv)
