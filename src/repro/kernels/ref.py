"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contracts: each kernel's test sweeps shapes/dtypes
and asserts allclose against the function here.  They are also the
lowering-friendly implementations used by the distributed (pjit) paths —
XLA fuses the broadcast+reduce patterns so no O(m*k*n) intermediate is
materialized, and GSPMD can shard them freely.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: precision of every f32 distance product (kernels and oracles alike):
#: left to the backend, a TPU computes an f32 dot in reduced precision
#: and XLA and the kernel compiler need not reduce it the same way
DOT_PRECISION = jax.lax.Precision.HIGHEST


def minplus_ref(a: jax.Array, b: jax.Array, *, chunk: int = 256) -> jax.Array:
    """Tropical (min-plus) matrix product: C[i,j] = min_k A[i,k] + B[k,j].

    Computed in k-chunks so the broadcasted intermediate stays bounded at
    (m, chunk, n) pre-fusion; XLA fuses broadcast-add with the min-reduce.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    chunk = min(chunk, k)
    if k % chunk:
        pad = chunk - k % chunk
        a = jnp.pad(a, ((0, 0), (0, pad)), constant_values=jnp.inf)
        b = jnp.pad(b, ((0, pad), (0, 0)), constant_values=jnp.inf)
        k += pad
    steps = k // chunk

    def body(c, acc):
        ak = jax.lax.dynamic_slice(a, (0, c * chunk), (m, chunk))
        bk = jax.lax.dynamic_slice(b, (c * chunk, 0), (chunk, n))
        part = jnp.min(ak[:, :, None] + bk[None, :, :], axis=1)
        return jnp.minimum(acc, part)

    init = jnp.full((m, n), jnp.inf, dtype=a.dtype)
    return jax.lax.fori_loop(0, steps, body, init)


def minplus_update_ref(
    g: jax.Array, c: jax.Array, r: jax.Array, *, chunk: int = 256
) -> jax.Array:
    """Fused min-plus update: O[i,j] = min(G[i,j], min_k C[i,k] + R[k,j]).

    Identical accumulation order to :func:`minplus_ref` but seeded from G,
    so ``minplus_update_ref(g, c, r) == minimum(g, minplus_ref(c, r))``
    bit-for-bit (min is exact) while the (m, n) product intermediate is
    never formed outside the fused loop.
    """
    m, n = g.shape
    m2, k = c.shape
    k2, n2 = r.shape
    assert (m, n) == (m2, n2) and k == k2, (g.shape, c.shape, r.shape)
    chunk = min(chunk, k)
    if k % chunk:
        pad = chunk - k % chunk
        c = jnp.pad(c, ((0, 0), (0, pad)), constant_values=jnp.inf)
        r = jnp.pad(r, ((0, pad), (0, 0)), constant_values=jnp.inf)
        k += pad
    steps = k // chunk

    def body(s, acc):
        ck = jax.lax.dynamic_slice(c, (0, s * chunk), (m, chunk))
        rk = jax.lax.dynamic_slice(r, (s * chunk, 0), (chunk, n))
        part = jnp.min(ck[:, :, None] + rk[None, :, :], axis=1)
        return jnp.minimum(acc, part)

    return jax.lax.fori_loop(0, steps, body, g)


def minplus_panel_row_ref(
    d: jax.Array, r: jax.Array, *, chunk: int = 256
) -> jax.Array:
    """Fused Phase-2 row-panel oracle: R' = min(R, D (x) R).

    d (b, b), r (b, n) -> (b, n).  Delegates to
    :func:`minplus_update_ref` with R as both seed and contraction
    operand - the accumulation is seeded from R, so no (b, n) product
    intermediate exists, and because min is exact the result is
    bit-identical to the Pallas panel kernel for any tiling.
    """
    b, b2 = d.shape
    assert b == b2 == r.shape[0], (d.shape, r.shape)
    return minplus_update_ref(r, d, r, chunk=chunk)


def minplus_panel_col_ref(
    c: jax.Array, d: jax.Array, *, chunk: int = 256
) -> jax.Array:
    """Fused Phase-2 column-panel oracle: C' = min(C, C (x) D).

    c (m, b), d (b, b) -> (m, b).  See :func:`minplus_panel_row_ref`.
    """
    b, b2 = d.shape
    assert b == b2 == c.shape[1], (c.shape, d.shape)
    return minplus_update_ref(c, c, d, chunk=chunk)


def minplus_border_ref(
    e: jax.Array, a: jax.Array, *, chunk: int = 256
) -> jax.Array:
    """Fused border-relaxation oracle: B = min(E, E (x) A).

    e (m, n), a (n, n) -> (m, n).  Delegates to
    :func:`minplus_update_ref` with E as both seed and first contraction
    operand - the accumulation is seeded from E, so no (m, n) product
    intermediate exists, and because min is exact the result is
    bit-identical to the Pallas border kernel for any tiling.
    """
    m, n = e.shape
    assert a.shape == (n, n), (e.shape, a.shape)
    return minplus_update_ref(e, e, a, chunk=chunk)


def frontier_relax_ref(
    dist: jax.Array,
    nbr: jax.Array,
    w: jax.Array,
    hi,
    *,
    chunk: int = 4096,
) -> jax.Array:
    """Masked sparse frontier-relaxation oracle (one delta-stepping sweep).

    O[j, q] = min(D[j, q], min_d mask(D[nbr[j, d], q]) + w[j, d]) with
    mask(x) = x where x < hi else +inf.  dist (n, s) nodes-major (the s
    sources on the last axis), nbr (n, deg) int32, w (n, deg) -> (n, s);
    padded CSR lanes carry w = +inf so they never win the min.

    Replays the Pallas kernel's op sequence per element (gather ->
    threshold mask -> broadcast-add -> min over the neighbour slots ->
    seed-min), so the result is bit-identical to
    :func:`repro.kernels.frontier.frontier_relax` for any node tiling:
    min is exact and the add is a single rounding per term in both.
    Computed in node chunks so the (chunk, deg, s) gather intermediate
    stays bounded.
    """
    n, s = dist.shape
    n2, deg = nbr.shape
    assert n == n2 and w.shape == nbr.shape, (dist.shape, nbr.shape, w.shape)
    hi = jnp.asarray(hi, dist.dtype)
    chunk = min(chunk, n)
    pad = -n % chunk
    dist_p = dist
    if pad:
        # padded nodes: dist +inf, edges to node 0 with weight +inf — they
        # relax to +inf and are sliced off, never touching real rows
        dist_p = jnp.pad(dist, ((0, pad), (0, 0)), constant_values=jnp.inf)
        nbr = jnp.pad(nbr, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)), constant_values=jnp.inf)
    steps = (n + pad) // chunk

    def body(c, out):
        ni = jax.lax.dynamic_slice(nbr, (c * chunk, 0), (chunk, deg))
        wi = jax.lax.dynamic_slice(w, (c * chunk, 0), (chunk, deg))
        g = jnp.take(dist_p, ni, axis=0)                # (chunk, deg, s)
        g = jnp.where(g < hi, g, jnp.inf)
        cand = jnp.min(g + wi[:, :, None], axis=1)      # (chunk, s)
        cur = jax.lax.dynamic_slice(dist_p, (c * chunk, 0), (chunk, s))
        return jax.lax.dynamic_update_slice(
            out, jnp.minimum(cur, cand), (c * chunk, 0)
        )

    out = jax.lax.fori_loop(0, steps, body, jnp.zeros_like(dist_p))
    return out[:n] if pad else out


def floyd_warshall_ref(d: jax.Array) -> jax.Array:
    """In-block Floyd-Warshall: all-pairs shortest paths on a dense block.

    d[i,j] is the edge weight (inf when absent); diagonal is assumed 0 (it is
    clamped here for safety).
    """
    n = d.shape[0]
    d = jnp.minimum(d, jnp.where(jnp.eye(n, dtype=bool), 0.0, jnp.inf))

    def body(k, dist):
        return jnp.minimum(dist, dist[:, k][:, None] + dist[k, :][None, :])

    return jax.lax.fori_loop(0, n, body, d)


def pairwise_sq_dists_ref(x: jax.Array, y: jax.Array) -> jax.Array:
    """Squared Euclidean distances between rows of x (m,D) and y (n,D)."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    y2 = jnp.sum(y * y, axis=1, keepdims=True)
    xy = jax.lax.dot_general(
        x, y, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=DOT_PRECISION, preferred_element_type=jnp.float32,
    )
    return jnp.maximum(x2 + y2.T - 2.0 * xy, 0.0)


@jax.jit
def sq_norms(x: jax.Array) -> jax.Array:
    """Squared row norms (m, 1) f32 of the kNN kernel's operands.

    :func:`repro.kernels.ops.knn_topk` computes them once per call and
    hands the same array to the kernel or to :func:`knn_topk_ref`: a
    row's sum can round differently in two programs (XLA's CPU backend
    orders a reduction by the row's position in the array, and contracts
    a fused multiply-add chain in some fusions and not others), so norms
    recomputed inside each program need not agree bit for bit.
    """
    x = x.astype(jnp.float32)
    return jnp.sum(x * x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_smallest_ref(d: jax.Array, k: int):
    """Indices+values of the k smallest entries per row of d."""
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def topk_by_index(d: jax.Array, i: jax.Array, k: int):
    """The k smallest (distance, index) pairs of each row, ranked by
    distance and then index: the kNN lists' tie-break, which does not
    depend on the order the candidates are laid out in."""
    sd, si = jax.lax.sort((d, i), dimension=1, num_keys=2)
    return sd[:, :k], si[:, :k]


@functools.partial(jax.jit, static_argnames=("chunk",))
def knn_topk_ref(
    x: jax.Array,
    y: jax.Array,
    seed_d: jax.Array,
    seed_i: jax.Array,
    x2: jax.Array,
    y2: jax.Array,
    *,
    row0=0,
    col0=0,
    n_valid=None,
    chunk: int = 256,
):
    """Chunked oracle of the fused top-k kNN kernel
    (:func:`repro.kernels.knn_topk.knn_topk`).

    x (m, D) query rows at global offset ``row0``, with squared norms
    x2 (m, 1); y (n, D) candidate rows, with squared norms y2 (n, 1) (both
    from :func:`sq_norms`), at global
    rows at global column offset ``col0``; seed_d/seed_i (m, k) the
    incoming candidate lists ((+inf, -1) when empty).  Columns at or
    beyond ``n_valid`` (global count, default ``col0 + n``) are masked,
    as is each row's self-match.  Returns (dists, idx), each (m, k),
    ranked by (distance, then column index) (:func:`topk_by_index`), so
    a tie goes to the smaller column whether it came in the seed list or
    in ``y``.

    Bit-identical to the Pallas kernel for any (chunk vs bm/bn) tiling:
    both are handed the same row norms, the distance tile replays the kernel's exact op sequence
    (full-depth product at :data:`DOT_PRECISION`, x2 + y2 - 2xy, clamp at
    zero — min/compare are exact, one rounding per add), and the per-chunk
    fold (the chunk's own top k, then :func:`topk_by_index` with the
    running list) makes the (value, index) selection the kernel's k-step
    extraction does: selection under a total order is prefix-stable, so
    folding in any chunk size yields the whole-stream answer.
    """
    m, dfeat = x.shape
    n, d2 = y.shape
    assert dfeat == d2, (x.shape, y.shape)
    k = seed_d.shape[1]
    assert seed_d.shape == (m, k) and seed_i.shape == (m, k), (
        seed_d.shape, seed_i.shape,
    )
    col0 = jnp.asarray(col0, jnp.int32)
    hi = col0 + n if n_valid is None else jnp.minimum(
        col0 + n, jnp.asarray(n_valid, jnp.int32)
    )
    chunk = min(chunk, n)
    pad = -n % chunk
    y_p = jnp.pad(y, ((0, pad), (0, 0))) if pad else y
    y2_p = jnp.pad(y2, ((0, pad), (0, 0))) if pad else y2
    steps = (n + pad) // chunk
    x32 = x.astype(jnp.float32)
    rows = jnp.asarray(row0, jnp.int32) + jnp.arange(m, dtype=jnp.int32)[
        :, None
    ]

    def body(c, carry):
        bd, bi = carry
        yc = jax.lax.dynamic_slice_in_dim(
            y_p, c * chunk, chunk, 0
        ).astype(jnp.float32)
        y2 = jax.lax.dynamic_slice_in_dim(y2_p, c * chunk, chunk, 0)
        xy = jax.lax.dot_general(
            x32, yc,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=DOT_PRECISION,
            preferred_element_type=jnp.float32,
        )
        d = jnp.maximum(x2 + y2.T - 2.0 * xy, 0.0)
        cols = col0 + c * chunk + jnp.arange(chunk, dtype=jnp.int32)[
            None, :
        ]
        dead = (rows == cols) | (cols >= hi)
        d = jnp.where(dead, jnp.inf, d)
        ci = jnp.where(dead, -1, jnp.broadcast_to(cols, d.shape))
        # the chunk's columns ascend, so lax.top_k's first-wins tie rule
        # already ranks them by (distance, index); only its best k can
        # reach the merged list
        neg, pos = jax.lax.top_k(-d, min(k, chunk))
        return topk_by_index(
            jnp.concatenate([bd, -neg], axis=1),
            jnp.concatenate([bi, jnp.take_along_axis(ci, pos, axis=1)],
                            axis=1),
            k,
        )

    return jax.lax.fori_loop(
        0, steps, body,
        (seed_d.astype(jnp.float32), seed_i.astype(jnp.int32)),
    )
