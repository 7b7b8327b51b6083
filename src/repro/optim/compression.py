"""Gradient compression for cross-pod data parallelism.

At multi-pod scale the DP gradient all-reduce crosses the (slow) inter-pod
links; int8 quantization with error feedback (1-bit-Adam family) cuts that
traffic 4x at negligible quality cost.  ``error_feedback_allreduce`` is a
shard_map building block: quantize (with the residual from the previous
step folded in), psum the int32 accumulators over the pod axis, dequantize,
and keep the new residual.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Tree = Any


def compress_decompress(g: jax.Array):
    """Symmetric per-tensor int8 quantization; returns (deq, residual)."""
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    return deq, g - deq


def error_feedback_allreduce(grads: Tree, residual: Tree, axis: str):
    """Compressed mean-all-reduce over `axis` (call inside shard_map).

    residual carries the per-leaf quantization error into the next step
    (error feedback), which is what keeps convergence unharmed.
    Returns (reduced_grads, new_residual).
    """
    size = jax.lax.axis_size(axis)

    def one(g, r):
        gf = g.astype(jnp.float32) + r
        # shared scale across the group (one scalar pmax) so the int32
        # accumulator dequantizes exactly: sum_i q_i * s == (sum_i q_i) * s
        scale = jax.lax.pmax(jnp.max(jnp.abs(gf)), axis) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int32)
        new_r = gf - q.astype(jnp.float32) * scale
        q_sum = jax.lax.psum(q, axis)           # int32 accumulator
        g_red = q_sum.astype(jnp.float32) * scale / size
        return g_red.astype(g.dtype), new_r

    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = treedef.flatten_up_to(residual)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return (
        jax.tree.unflatten(treedef, [o[0] for o in out]),
        jax.tree.unflatten(treedef, [o[1] for o in out]),
    )
