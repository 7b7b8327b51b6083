"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp
oracle, swept over shapes and dtypes."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.minplus import minplus as mp_pallas
from repro.kernels.minplus_border import minplus_border as mb_pallas
from repro.kernels.minplus_update import minplus_update as mpu_pallas
from repro.kernels.minplus_panel import (
    minplus_panel_col as mpc_pallas,
    minplus_panel_row as mpr_pallas,
)
from repro.kernels.floyd_warshall import floyd_warshall as fw_pallas
from repro.kernels.knn_topk import PAD_IDX
from repro.kernels.pairwise_dist import pairwise_sq_dists as pd_pallas


MINPLUS_TILINGS = [
    (32, 32, 32, 32, 32, 32, 4),
    (64, 128, 96, 32, 32, 64, 8),
    (128, 64, 128, 64, 64, 32, 8),
    (256, 256, 256, 128, 128, 128, 16),
    (8, 8, 8, 8, 8, 8, 1),
    # register sub-tiles: one 8-row group over a 1024-lane block; 24
    # rows (three groups) over 384 lanes and a 96-deep contraction;
    # 24 rows over one 128-lane strip, two contraction steps
    (16, 128, 1024, 8, 1024, 128, 8),
    (48, 96, 384, 24, 384, 96, 8),
    (24, 256, 256, 24, 128, 128, 8),
]


@pytest.mark.parametrize("m,k,n,bm,bn,bk,unroll", MINPLUS_TILINGS)
def test_minplus_matches_ref(m, k, n, bm, bn, bk, unroll, rng):
    a = rng.uniform(0, 10, (m, k)).astype(np.float32)
    b = rng.uniform(0, 10, (k, n)).astype(np.float32)
    want = np.min(a[:, :, None] + b[None, :, :], axis=1)
    got = mp_pallas(a, b, bm=bm, bn=bn, bk=bk, unroll=unroll, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(ref.minplus_ref(a, b), want, rtol=1e-6)
    assert np.array_equal(np.asarray(got), np.asarray(ref.minplus_ref(a, b)))


def test_minplus_with_inf(rng):
    a = rng.uniform(0, 5, (32, 32)).astype(np.float32)
    a[a < 1.0] = np.inf
    b = rng.uniform(0, 5, (32, 32)).astype(np.float32)
    want = np.min(a[:, :, None] + b[None, :, :], axis=1)
    got = mp_pallas(a, b, bm=32, bn=32, bk=32, unroll=4, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("inf", [False, True], ids=["finite", "inf"])
@pytest.mark.parametrize("m,k,n,bm,bn,bk,unroll", MINPLUS_TILINGS)
def test_minplus_update_matches_ref(m, k, n, bm, bn, bk, unroll, inf, rng):
    """The APSP update O = min(E, C (x) R), its accumulator seeded from E
    as the fit seeds it, bit-identical to the oracle at every tiling of
    the plain product; the inf inputs carry +inf entries, as the graph
    does before closure."""
    e = rng.uniform(0, 20, (m, n)).astype(np.float32)
    c = rng.uniform(0, 10, (m, k)).astype(np.float32)
    r = rng.uniform(0, 10, (k, n)).astype(np.float32)
    if inf:
        for x in (e, c, r):
            x[rng.uniform(size=x.shape) < 0.3] = np.inf
    want = np.minimum(e, np.min(c[:, :, None] + r[None, :, :], axis=1))
    got = np.asarray(mpu_pallas(
        e, c, r, bm=bm, bn=bn, bk=bk, unroll=unroll, interpret=True
    ))
    oracle = np.asarray(ref.minplus_update_ref(e, c, r))
    assert np.array_equal(got, oracle)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert np.array_equal(np.isinf(got), np.isinf(want))


def _closed_diag_block(rng, b):
    """A Floyd-Warshall-closed (b, b) block (zero diagonal), as Phase 2
    sees the diagonal block."""
    d = rng.uniform(1, 10, (b, b)).astype(np.float32)
    return np.asarray(ref.floyd_warshall_ref(d))


@pytest.mark.parametrize(
    "b,n,bm,bn,bk,unroll",
    [
        (32, 32, 32, 32, 32, 4),
        (64, 192, 32, 64, 32, 8),
        (128, 128, 64, 128, 128, 16),
        (8, 8, 8, 8, 8, 1),
        (24, 1024, 24, 1024, 24, 8),
        (128, 384, 8, 384, 128, 8),
    ],
)
def test_minplus_panel_row_matches_ref(b, n, bm, bn, bk, unroll, rng):
    d = _closed_diag_block(rng, b)
    r = rng.uniform(0, 30, (b, n)).astype(np.float32)
    want = np.minimum(r, np.min(d[:, :, None] + r[None, :, :], axis=1))
    got = mpr_pallas(d, r, bm=bm, bn=bn, bk=bk, unroll=unroll,
                     interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # bit-identical to the oracle (min is exact): the acceptance contract
    assert np.array_equal(np.asarray(got),
                          np.asarray(ref.minplus_panel_row_ref(d, r)))


@pytest.mark.parametrize(
    "m,b,bm,bn,bk,unroll",
    [
        (32, 32, 32, 32, 32, 4),
        (192, 64, 64, 32, 64, 8),
        (128, 128, 128, 64, 32, 2),
        (8, 8, 8, 8, 8, 1),
        (48, 128, 24, 128, 128, 8),
        (16, 96, 8, 96, 96, 8),
    ],
)
def test_minplus_panel_col_matches_ref(m, b, bm, bn, bk, unroll, rng):
    d = _closed_diag_block(rng, b)
    c = rng.uniform(0, 30, (m, b)).astype(np.float32)
    want = np.minimum(c, np.min(c[:, :, None] + d[None, :, :], axis=1))
    got = mpc_pallas(c, d, bm=bm, bn=bn, bk=bk, unroll=unroll,
                     interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert np.array_equal(np.asarray(got),
                          np.asarray(ref.minplus_panel_col_ref(c, d)))


def test_minplus_panel_with_inf(rng):
    """+inf (missing edges) must ride through the fused panels."""
    d = _closed_diag_block(rng, 32)
    r = rng.uniform(0, 5, (32, 64)).astype(np.float32)
    r[r < 1.0] = np.inf
    want = np.minimum(r, np.min(d[:, :, None] + r[None, :, :], axis=1))
    got = mpr_pallas(d, r, bm=32, bn=32, bk=32, unroll=4, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize(
    "m,n,bm,bn,bk,unroll",
    [
        (8, 32, 8, 32, 32, 4),
        (16, 128, 8, 64, 32, 8),
        (64, 64, 64, 64, 64, 16),
        (8, 8, 8, 8, 8, 1),
        (8, 1024, 8, 1024, 128, 8),
        (24, 384, 24, 384, 128, 8),
    ],
)
def test_minplus_border_matches_ref(m, n, bm, bn, bk, unroll, rng):
    """Border relaxation B = min(E, E (x) A): Pallas vs oracle, with inf
    (sparse edge rows) in the mix - the shape the absorb path runs."""
    a = _closed_diag_block(rng, n)
    e = rng.uniform(0, 30, (m, n)).astype(np.float32)
    e[e > 10.0] = np.inf
    want = np.minimum(e, np.min(e[:, :, None] + a[None, :, :], axis=1))
    got = mb_pallas(e, a, bm=bm, bn=bn, bk=bk, unroll=unroll,
                    interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert np.array_equal(np.asarray(got),
                          np.asarray(ref.minplus_border_ref(e, a)))


def test_minplus_border_equals_materializing_composition(rng):
    """Fused border == min(E, minplus(E, A)) bit for bit through the ops
    dispatch on every mode that executes here."""
    a = _closed_diag_block(rng, 64)
    e = rng.uniform(0, 30, (16, 64)).astype(np.float32)
    for mode in ("auto", "ref", "pallas"):
        got = ops.minplus_border(e, a, mode=mode)
        assert np.array_equal(
            np.asarray(got),
            np.asarray(jnp.minimum(e, ops.minplus(e, a, mode=mode))),
        )


def test_panel_equals_materializing_composition(rng):
    """min(R, D (x) R) fused == the materializing two-step, bit for bit,
    through the ops dispatch on every mode that executes here."""
    d = _closed_diag_block(rng, 64)
    r = rng.uniform(0, 30, (64, 128)).astype(np.float32)
    c = rng.uniform(0, 30, (128, 64)).astype(np.float32)
    for mode in ("auto", "ref", "pallas"):
        row = ops.minplus_panel_row(d, r, mode=mode)
        col = ops.minplus_panel_col(c, d, mode=mode)
        assert np.array_equal(
            np.asarray(row),
            np.asarray(jnp.minimum(r, ops.minplus(d, r, mode=mode))),
        )
        assert np.array_equal(
            np.asarray(col),
            np.asarray(jnp.minimum(c, ops.minplus(c, d, mode=mode))),
        )


@pytest.mark.parametrize(
    "op", ["minplus", "update", "panel_row", "panel_col", "border"]
)
def test_unaligned_long_dims_are_padded_exactly(op, rng):
    """A dim longer than autotune.WHOLE_DIM_MAX with no aligned divisor
    (a base grown by an absorb) is padded with +inf to tiles the chip
    accepts and stripped again: bit-identical to the oracle, +inf entries
    included."""
    def u(*shape):
        x = rng.uniform(0, 30, shape).astype(np.float32)
        x[x > 27.0] = np.inf
        return x

    d = _closed_diag_block(rng, 16)
    args, oracle = {
        "minplus": ((u(264, 16), u(16, 260)), ref.minplus_ref),
        "update": ((u(260, 264), u(260, 16), u(16, 264)),
                   ref.minplus_update_ref),
        "panel_row": ((d, u(16, 300)), ref.minplus_panel_row_ref),
        "panel_col": ((u(300, 16), d), ref.minplus_panel_col_ref),
        "border": ((u(8, 260), _closed_diag_block(rng, 260)),
                   ref.minplus_border_ref),
    }[op]
    fn = {"minplus": ops.minplus, "update": ops.minplus_update,
          "panel_row": ops.minplus_panel_row,
          "panel_col": ops.minplus_panel_col,
          "border": ops.minplus_border}[op]
    got = np.asarray(fn(*args, mode="pallas"))
    want = np.asarray(oracle(*args))
    assert got.shape == want.shape
    assert np.array_equal(got, want)


def test_tile_override_validation(rng):
    """Bad tile overrides raise a clear ValueError from ops.py, not a raw
    Pallas trace assertion - on every op that takes tiles, including the
    ref path (which would otherwise silently ignore them)."""
    g = rng.uniform(0, 10, (64, 64)).astype(np.float32)
    with pytest.raises(ValueError, match="bm=48 does not divide m=64"):
        ops.minplus_update(g, g, g, bm=48)
    with pytest.raises(ValueError, match="bn=24 does not divide n=64"):
        ops.minplus_panel_row(g, g, mode="ref", bn=24)
    with pytest.raises(ValueError, match="bk=40 does not divide k=64"):
        ops.minplus_panel_col(g, g, mode="ref", bk=40)
    with pytest.raises(ValueError, match="unroll=24 does not divide"):
        ops.minplus(g, g, bk=64, unroll=24)
    with pytest.raises(ValueError, match="unknown tile kwargs"):
        ops.minplus_update(g, g, g, block=32)
    with pytest.raises(ValueError, match="must be a positive int"):
        ops.minplus_update(g, g, g, bm=0)
    # valid overrides still go through (clamped like the kernels clamp)
    out = ops.minplus_update(g, g, g, bm=128, bn=32, bk=16, unroll=8)
    assert np.array_equal(
        np.asarray(out), np.asarray(ref.minplus_update_ref(g, g, g))
    )


@pytest.mark.parametrize("n", [8, 32, 64, 128])
def test_floyd_warshall_matches_scipy(n, rng):
    import scipy.sparse.csgraph as cs

    d = rng.uniform(1, 10, (n, n)).astype(np.float32)
    d = np.minimum(d, d.T)
    np.fill_diagonal(d, 0)
    # sparsify: drop 60% of edges
    mask = rng.uniform(size=(n, n)) < 0.6
    mask = mask | mask.T
    np.fill_diagonal(mask, False)
    d[mask] = np.inf
    want = cs.floyd_warshall(np.where(np.isfinite(d), d, 0))
    got = fw_pallas(d, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        ref.floyd_warshall_ref(d), want, rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize(
    "m,n,d,bm,bn,bd",
    [
        (16, 16, 8, 16, 16, 8),
        (48, 64, 20, 16, 16, 10),
        (64, 64, 784, 32, 32, 392),
        (128, 96, 32, 64, 32, 32),
    ],
)
def test_pairwise_matches_direct(m, n, d, bm, bn, bd, rng):
    x = rng.normal(size=(m, d)).astype(np.float32)
    y = rng.normal(size=(n, d)).astype(np.float32)
    want = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    got = pd_pallas(x, y, bm=bm, bn=bn, bd=bd, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pairwise_dtypes(dtype, rng):
    x = rng.normal(size=(32, 16)).astype(dtype)
    y = rng.normal(size=(32, 16)).astype(dtype)
    want = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    got = ops.pairwise_sq_dists(x, y, mode="ref")
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_ops_mode_dispatch(rng):
    a = rng.uniform(0, 10, (16, 16)).astype(np.float32)
    b = rng.uniform(0, 10, (16, 16)).astype(np.float32)
    for mode in ("auto", "ref", "pallas"):
        out = ops.minplus(a, b, mode=mode)
        np.testing.assert_allclose(
            out, np.min(a[:, :, None] + b[None, :, :], axis=1), rtol=1e-6
        )
    with pytest.raises(ValueError):
        ops.minplus(a, b, mode="bogus")


def test_pairwise_auto_shrinks_tiles(rng):
    """Shapes the static tile defaults do not divide auto-shrink to a
    legal tiling instead of crashing on the kernel's divisibility
    assert — including through the pallas (interpret) path."""
    x = rng.normal(size=(100, 3)).astype(np.float32)
    y = rng.normal(size=(52, 3)).astype(np.float32)
    want = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    for mode in ("auto", "ref", "pallas"):
        got = ops.pairwise_sq_dists(x, y, mode=mode)
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_pairwise_tile_override_validation(rng):
    """Explicit non-dividing tiles raise a clear ValueError naming the
    shapes and tiles, ops.py style, instead of a raw kernel assert."""
    x = rng.normal(size=(64, 8)).astype(np.float32)
    with pytest.raises(ValueError, match="bm=48 does not divide m=64"):
        ops.pairwise_sq_dists(x, x, bm=48)
    with pytest.raises(ValueError, match="bd=6 does not divide D=8"):
        ops.pairwise_sq_dists(x, x, bd=6)
    with pytest.raises(ValueError, match="unknown tile kwargs"):
        ops.pairwise_sq_dists(x, x, bk=8)
    with pytest.raises(ValueError, match="must be a positive int"):
        ops.pairwise_sq_dists(x, x, bn=0)
    with pytest.raises(ValueError, match="feature dims differ"):
        ops.pairwise_sq_dists(x, x[:, :4])
    # valid overrides still go through (clamped like the kernels clamp)
    out = ops.pairwise_sq_dists(x, x, bm=128, bn=32, bd=4)
    want = ((np.asarray(x)[:, None, :] - np.asarray(x)[None, :, :]) ** 2
            ).sum(-1)
    np.testing.assert_allclose(out, want, rtol=1e-3, atol=1e-4)


# ------------------------------------------------------- fused kNN top-k --


def _brute_knn(x, y, k, row0=0, col0=0, n_valid=None):
    """Brute-force (distance, column)-ranked top-k, ties to the smaller
    column: the independent witness both the kernel and the oracle must
    match.  Distances use the kernel's own f32 x2 + y2 - 2<x,y> form so
    that near-ties order identically (the tie rule is only meaningful on
    bitwise-equal values)."""
    x = x.astype(np.float32)
    y = y.astype(np.float32)
    x2 = (x * x).sum(1, keepdims=True)
    y2 = (y * y).sum(1, keepdims=True)
    d = np.maximum(x2 + y2.T - 2.0 * (x @ y.T), 0.0).astype(np.float32)
    rows = row0 + np.arange(x.shape[0])[:, None]
    cols = col0 + np.arange(y.shape[0])[None, :]
    hi = col0 + y.shape[0] if n_valid is None else min(
        col0 + y.shape[0], n_valid
    )
    dead = (rows == cols) | (cols >= hi)
    d = np.where(dead, np.inf, d)
    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    out_d = np.take_along_axis(d, order, axis=1)
    out_i = np.where(
        np.isfinite(out_d), (col0 + order).astype(np.int32), PAD_IDX
    )
    return out_d.astype(np.float32), out_i.astype(np.int32)


def _empty_seed(m, k):
    return (
        jnp.full((m, k), jnp.inf, jnp.float32),
        jnp.full((m, k), PAD_IDX, jnp.int32),
    )


@pytest.mark.parametrize(
    "m,n,d,k,bm,bn",
    [
        (32, 64, 3, 5, 32, 64),
        (64, 64, 8, 10, 16, 16),
        (48, 100, 4, 7, 32, 64),   # bn does not divide n: wrapper pads
        (100, 52, 6, 9, 64, 32),   # neither dim divides
        (8, 8, 2, 3, 8, 8),
    ],
)
def test_knn_topk_matches_oracle_and_brute(m, n, d, k, bm, bn, rng):
    """Kernel (interpret) vs chunked oracle vs independent brute force:
    bit-identical values AND indices across tilings, including tilings
    that do not divide the problem."""
    x = rng.normal(size=(m, d)).astype(np.float32)
    y = rng.normal(size=(n, d)).astype(np.float32)
    sd, si = _empty_seed(m, k)
    got_d, got_i = ops.knn_topk(x, y, sd, si, mode="pallas", bm=bm, bn=bn)
    ref_d, ref_i = ops.knn_topk(x, y, sd, si, mode="ref", bn=bn)
    assert np.array_equal(np.asarray(got_d), np.asarray(ref_d))
    assert np.array_equal(np.asarray(got_i), np.asarray(ref_i))
    want_d, want_i = _brute_knn(x, y, k)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(got_i), want_i)


def test_knn_topk_tie_breaking_on_duplicates(rng):
    """Duplicate points force exact distance ties; the tie rule (lower
    column index) must hold bit for bit on every tiling and in the
    oracle."""
    base = rng.normal(size=(16, 4)).astype(np.float32)
    y = np.concatenate([base, base, base])  # every row exists 3x
    x = base.copy()
    m, k = x.shape[0], 6
    sd, si = _empty_seed(m, k)
    outs = []
    for bm, bn in ((8, 16), (16, 48), (4, 12)):
        od, oi = ops.knn_topk(x, y, sd, si, mode="pallas", bm=bm, bn=bn)
        outs.append((np.asarray(od), np.asarray(oi)))
    rd, ri = ops.knn_topk(x, y, sd, si, mode="ref")
    outs.append((np.asarray(rd), np.asarray(ri)))
    want_d, want_i = _brute_knn(x, y, k)
    for od, oi in outs:
        assert np.array_equal(od, outs[0][0])
        assert np.array_equal(oi, outs[0][1])
    assert np.array_equal(outs[0][1], want_i)


@pytest.mark.parametrize("mode", ["pallas", "ref"])
def test_knn_topk_ties_independent_of_chaining_order(mode, rng):
    """Ties go to the smaller column however the columns arrive: folding
    the column shards in descending order through the seed lists (as a
    ring step on a later shard does) gives the lists of one call."""
    base = rng.normal(size=(16, 4)).astype(np.float32)
    y = np.concatenate([base, base, base])  # every row exists 3x
    x = base.copy()
    k = 6
    sd, si = _empty_seed(16, k)
    want_d, want_i = ops.knn_topk(x, y, sd, si, mode=mode, bm=8, bn=16)
    d, i = sd, si
    for c in (32, 16, 0):
        d, i = ops.knn_topk(x, y[c:c + 16], d, i, col0=c, mode=mode,
                            bm=8, bn=16)
    assert np.array_equal(np.asarray(d), np.asarray(want_d))
    assert np.array_equal(np.asarray(i), np.asarray(want_i))
    assert np.array_equal(np.asarray(want_i), _brute_knn(x, y, k)[1])


def test_knn_topk_k_exceeds_candidates(rng):
    """k > live candidates: the tail must be (+inf, PAD_IDX) identically
    in kernel and oracle (self-match masked, so n-1 live per row)."""
    x = rng.normal(size=(8, 3)).astype(np.float32)
    k = 12  # > n - 1 = 7 live candidates
    sd, si = _empty_seed(8, k)
    for mode in ("pallas", "ref"):
        od, oi = ops.knn_topk(x, x, sd, si, mode=mode, bm=8, bn=8)
        od, oi = np.asarray(od), np.asarray(oi)
        assert np.isfinite(od[:, :7]).all()
        assert (od[:, 7:] == np.inf).all()
        assert (oi[:, 7:] == PAD_IDX).all()


def test_knn_topk_padded_columns_all_dead(rng):
    """n_valid masking: columns at or beyond the global bound are dead;
    with n_valid <= col0 every lane is dead and rows come back all
    (+inf, PAD_IDX)."""
    x = rng.normal(size=(8, 3)).astype(np.float32)
    y = rng.normal(size=(16, 3)).astype(np.float32)
    sd, si = _empty_seed(8, 4)
    for mode in ("pallas", "ref"):
        od, oi = ops.knn_topk(
            x, y, sd, si, col0=100, n_valid=100, mode=mode, bm=8, bn=16
        )
        assert (np.asarray(od) == np.inf).all()
        assert (np.asarray(oi) == PAD_IDX).all()
    # partial masking agrees with brute force on the live prefix
    for mode in ("pallas", "ref"):
        od, oi = ops.knn_topk(
            x, y, sd, si, n_valid=9, mode=mode, bm=8, bn=16
        )
        want_d, want_i = _brute_knn(x, y, 4, n_valid=9)
        np.testing.assert_allclose(od, want_d, rtol=1e-5, atol=1e-5)
        assert np.array_equal(np.asarray(oi), want_i)


def test_knn_topk_seed_chaining_equals_one_shot(rng):
    """Folding the columns in two seeded calls == one call over all
    columns, bit for bit — the prefix-stability that makes the kernel
    composable across column tiles and ring steps."""
    x = rng.normal(size=(16, 5)).astype(np.float32)
    y = rng.normal(size=(48, 5)).astype(np.float32)
    k = 6
    sd, si = _empty_seed(16, k)
    for mode in ("pallas", "ref"):
        one_d, one_i = ops.knn_topk(x, y, sd, si, mode=mode, bm=16, bn=16)
        ad, ai = ops.knn_topk(x, y[:32], sd, si, mode=mode, bm=16, bn=16)
        bd, bi = ops.knn_topk(
            x, y[32:], ad, ai, col0=32, mode=mode, bm=16, bn=16
        )
        assert np.array_equal(np.asarray(one_d), np.asarray(bd))
        assert np.array_equal(np.asarray(one_i), np.asarray(bi))


def test_knn_topk_validation(rng):
    x = rng.normal(size=(16, 4)).astype(np.float32)
    y = rng.normal(size=(16, 5)).astype(np.float32)
    sd, si = _empty_seed(16, 3)
    with pytest.raises(ValueError, match="feature dims differ"):
        ops.knn_topk(x, y, sd, si)
    with pytest.raises(ValueError, match="must be \\(m=16, k\\)"):
        ops.knn_topk(x, x, sd[:8], si[:8])
    with pytest.raises(ValueError, match="must match seed_d"):
        ops.knn_topk(x, x, sd, si[:, :2])
    with pytest.raises(ValueError, match="unknown tile kwargs"):
        ops.knn_topk(x, x, sd, si, bk=8)
    with pytest.raises(ValueError, match="must be a positive int"):
        ops.knn_topk(x, x, sd, si, bm=-2)
