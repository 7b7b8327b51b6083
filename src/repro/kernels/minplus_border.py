"""Fused border relaxation for incremental geodesic updates (Pallas TPU).

When m stream arrivals are folded into a fitted (n, n) geodesic system
(:mod:`repro.core.update`), the first step relaxes the new points' edge
rows through the *closed* base matrix:

  border      B <- min(E, E (x) A)     E (m, n) edges, A (n, n) closed

Composed from the plain :mod:`repro.kernels.minplus` kernel this
materializes the full (m, n) min-plus product in HBM before the
elementwise min.  The fused form is the same seeded accumulation the
Phase-2/Phase-3 kernels use - the output tile is seeded from E's tile at
contraction step 0 and the rank-1 updates accumulate into it in
registers - so the border IS :mod:`repro.kernels.minplus_update` with the
edge panel bound as both seed and first contraction operand:

  minplus_border(e, a) == minplus_update(e, e, a)

(The remaining expansion steps reuse the existing fused kernels: the
new-block closure seeds from F, the closed-border sweep is
``minplus_panel_row`` with the (m, m) block as diagonal, and the interior
rank-m sweep is ``minplus_update`` - no step materializes a min-plus
intermediate, in particular no (n, n) one.)

Bit-exactness: min is exact and order-independent and every contraction
term is a single rounded addition computed identically in every
schedule, so the result is bit-identical to
:func:`repro.kernels.ref.minplus_border_ref` for any tiling.
"""
from __future__ import annotations

import jax

from repro.kernels.minplus_update import minplus_update


def minplus_border(
    e: jax.Array,
    a: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    unroll: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Fused border relaxation B = min(E, E (x) A).

    Shapes: e (m, n), a (n, n) -> (m, n).  E is both the seed and the
    first contraction operand; no (m, n) product intermediate is
    materialized.  A must be square (the closed base system).
    """
    m, n = e.shape
    assert a.shape == (n, n), (e.shape, a.shape)
    return minplus_update(
        e, e, a, bm=bm, bn=bn, bk=bk, unroll=unroll, interpret=interpret
    )
