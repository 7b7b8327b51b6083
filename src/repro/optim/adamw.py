"""AdamW + schedule + gradient clipping, dependency-free.

The state is ``{"m": tree, "v": tree, "step": int32 scalar}``, with m and
v zero trees shaped like the parameters (the stress objective of
:mod:`repro.core.embedding` builds it that way).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Tree = Any


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def cosine_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    # warmup_steps == 0 means NO warmup ramp: full lr from step 0 (the
    # naive step/max(w, 1) would make the step-0 lr exactly 0)
    if cfg.warmup_steps > 0:
        warm = jnp.minimum(step / cfg.warmup_steps, 1.0)
    else:
        warm = jnp.ones_like(jnp.asarray(step, jnp.float32))
    decay_steps = cfg.total_steps - cfg.warmup_steps
    if decay_steps > 0:
        prog = jnp.clip((step - cfg.warmup_steps) / decay_steps, 0.0, 1.0)
    else:
        # total_steps == warmup_steps: there is no decay phase - hold at
        # full lr instead of collapsing to min_lr_frac one step in
        prog = jnp.zeros_like(jnp.asarray(step, jnp.float32))
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def global_norm(tree: Tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def adamw_update(cfg: AdamWConfig, grads: Tree, state: Tree, params: Tree):
    """Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    lr = cosine_schedule(cfg, step)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    b1, b2 = cfg.b1, cfg.b2

    def upd(g, m, v, p):
        g = g.astype(jnp.float32) * scale
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * jnp.square(g)
        m_hat = m_new / (1 - b1**step)
        v_hat = v_new / (1 - b2**step)
        delta = m_hat / (jnp.sqrt(v_hat) + cfg.eps)
        p_new = p - lr * (delta + cfg.weight_decay * p)
        return p_new.astype(p.dtype), m_new, v_new

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_state = {
        "m": jax.tree.unflatten(treedef, [o[1] for o in out]),
        "v": jax.tree.unflatten(treedef, [o[2] for o in out]),
        "step": step,
    }
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
