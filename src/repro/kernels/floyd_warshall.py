"""Pallas TPU kernel: in-VMEM blocked Floyd-Warshall (APSP phase 1).

The b x b diagonal block of the distance matrix lives entirely in VMEM and
is swept with rank-1 min-plus updates, one per pivot k.  This is the
critical-path step of the communication-avoiding APSP schedule (paper
SIII-B / Solomonik et al.): it is sequential in k by nature, so the kernel
keeps the whole working set on-core and the surrounding phases supply all
the parallelism.

The pivot row and column are read from the working block by a masked
min over the other rows / columns (exact: every other entry is +inf), as
the TPU lowering has no dynamic slice of a loaded value.  The block, its
copy and the loop's temporaries are budgeted at :data:`VMEM_WORDS` words
per element: the kernel asks for that much scoped VMEM (at least the
16 MiB default, at most 32 MiB of the v5e core's 128 MiB at
:data:`MAX_BLOCK`), and the v5e compiler accepts blocks of 128 up to
1024 so; a larger block is refused here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: scoped-VMEM words requested per block element (input, output, the
#: carried block and the masked-reduction temporaries)
VMEM_WORDS = 8
#: largest block the kernel takes (8 * 1024^2 f32 words = 32 MiB)
MAX_BLOCK = 1024


def _fw_kernel(d_ref, o_ref):
    n = d_ref.shape[0]
    d = d_ref[...]
    # clamp the diagonal to zero (a node is at distance 0 from itself)
    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    d = jnp.where(ii == jj, 0.0, d)

    def body(k, dist):
        row = jnp.min(jnp.where(ii == k, dist, jnp.inf), axis=0,
                      keepdims=True)                          # (1, n)
        col = jnp.min(jnp.where(jj == k, dist, jnp.inf), axis=1,
                      keepdims=True)                          # (n, 1)
        return jnp.minimum(dist, col + row)

    o_ref[...] = jax.lax.fori_loop(0, n, body, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def floyd_warshall(d: jax.Array, *, interpret: bool = False) -> jax.Array:
    """All-pairs shortest paths on a dense (b, b) block; inf = no edge."""
    n, n2 = d.shape
    assert n == n2, d.shape
    if n > MAX_BLOCK:
        raise ValueError(
            f"floyd_warshall: block {n} exceeds MAX_BLOCK={MAX_BLOCK} "
            "(the whole block is held in VMEM)"
        )
    vmem = max(16 * 2**20, VMEM_WORDS * n * n * d.dtype.itemsize)
    return pl.pallas_call(
        _fw_kernel,
        out_shape=jax.ShapeDtypeStruct((n, n), d.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )(d)
