"""Property-based tests (hypothesis) for the system's algebraic invariants."""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis", reason="hypothesis not installed in this container"
)
from hypothesis import given, settings, strategies as st  # noqa: E402
from hypothesis.extra import numpy as hnp  # noqa: E402

from repro.core import centering, metrics
from repro.kernels import ref

# integer-valued floats dodge fp non-associativity in semiring checks
_vals = st.integers(min_value=0, max_value=50).map(float)


def _mat(n, m):
    return hnp.arrays(np.float32, (n, m), elements=_vals)


@settings(max_examples=25, deadline=None)
@given(a=_mat(6, 5), b=_mat(5, 7), c=_mat(7, 4))
def test_minplus_associative(a, b, c):
    ab_c = ref.minplus_ref(ref.minplus_ref(a, b), c)
    a_bc = ref.minplus_ref(a, ref.minplus_ref(b, c))
    np.testing.assert_allclose(np.asarray(ab_c), np.asarray(a_bc))


@settings(max_examples=25, deadline=None)
@given(a=_mat(6, 6))
def test_minplus_identity(a):
    """Identity of (min,+): 0 on the diagonal, inf elsewhere."""
    n = a.shape[0]
    e = np.where(np.eye(n, dtype=bool), 0.0, np.inf).astype(np.float32)
    np.testing.assert_allclose(np.asarray(ref.minplus_ref(e, a)), a)
    np.testing.assert_allclose(np.asarray(ref.minplus_ref(a, e)), a)


@settings(max_examples=20, deadline=None)
@given(d=_mat(8, 8))
def test_floyd_warshall_idempotent_and_triangle(d):
    d = np.minimum(d, d.T) + 1.0
    np.fill_diagonal(d, 0.0)
    sp = np.asarray(ref.floyd_warshall_ref(d))
    # idempotence: shortest paths of shortest paths are unchanged
    sp2 = np.asarray(ref.floyd_warshall_ref(sp.copy()))
    np.testing.assert_allclose(sp2, sp, rtol=1e-6)
    # triangle inequality
    n = sp.shape[0]
    tri = sp[:, :, None] <= sp[:, None, :] + sp[None, :, :] + 1e-4
    assert tri.all()
    # dominated by direct edges
    assert (sp <= d + 1e-5).all()


@settings(max_examples=20, deadline=None)
@given(
    a=hnp.arrays(
        np.float32, (12, 12),
        elements=st.floats(0, 100, width=32),
    )
)
def test_double_center_zero_means(a):
    a = np.maximum(a, a.T)  # symmetric like a distance matrix
    b = np.asarray(centering.double_center(jnp.asarray(a)))
    np.testing.assert_allclose(b.mean(axis=0), 0.0, atol=1e-3)
    np.testing.assert_allclose(b.mean(axis=1), 0.0, atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(
    x=hnp.arrays(
        np.float32, (30, 3),
        elements=st.floats(-10, 10, width=32),
    ),
    scale=st.floats(0.5, 4.0),
    tx=st.floats(-5, 5),
)
def test_procrustes_similarity_invariant(x, scale, tx):
    if np.linalg.norm(x - x.mean(0)) < 1e-3:
        return  # degenerate cloud
    y = x * scale + tx
    err = float(metrics.procrustes_error(jnp.asarray(x), jnp.asarray(y)))
    assert err < 1e-5


@settings(max_examples=15, deadline=None)
@given(
    x=hnp.arrays(
        np.float32, (20, 6),
        elements=st.floats(-5, 5, width=32),
    )
)
def test_pairwise_nonneg_symmetric_zero_diag(x):
    d = np.asarray(ref.pairwise_sq_dists_ref(jnp.asarray(x), jnp.asarray(x)))
    assert (d >= 0).all()
    np.testing.assert_allclose(d, d.T, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.diag(d), 0.0, atol=1e-3)


# --------------------------------------------- consistent-hash router --

from repro.launch.router import ConsistentHashRouter, stable_hash  # noqa: E402


@settings(max_examples=20, deadline=None)
@given(
    n_nodes=st.integers(2, 8),
    seed=st.integers(0, 10_000),
)
def test_router_load_within_2x_of_uniform(n_nodes, seed):
    """With the default 64 vnodes per replica, every replica's share of
    a large keyspace stays within 2x of uniform."""
    nodes = [f"replica-{seed}-{i}" for i in range(n_nodes)]
    router = ConsistentHashRouter(nodes)
    n_keys = 2000
    counts = router.spread(f"key-{seed}-{j}" for j in range(n_keys))
    uniform = n_keys / n_nodes
    assert set(counts) == set(nodes)
    for node, c in counts.items():
        assert uniform / 2 < c < uniform * 2, (node, c, dict(counts))


@settings(max_examples=25, deadline=None)
@given(
    n_nodes=st.integers(2, 8),
    victim=st.integers(0, 7),
    seed=st.integers(0, 10_000),
)
def test_router_removal_is_minimal_reshuffle(n_nodes, victim, seed):
    """Removing one replica remaps EXACTLY the keys it owned (~1/N of
    the space) - every other key keeps its replica."""
    nodes = [f"replica-{seed}-{i}" for i in range(n_nodes)]
    gone = nodes[victim % n_nodes]
    router = ConsistentHashRouter(nodes)
    keys = [f"key-{seed}-{j}" for j in range(1000)]
    before = {k: router.route(k) for k in keys}
    router.remove(gone)
    moved = 0
    for k in keys:
        after = router.route(k)
        if after != before[k]:
            moved += 1
            assert before[k] == gone, (k, before[k], after)
        else:
            assert before[k] != gone
    assert moved == sum(1 for v in before.values() if v == gone)


@settings(max_examples=30, deadline=None)
@given(key=st.one_of(st.text(), st.integers(), st.binary()))
def test_stable_hash_is_deterministic_64bit(key):
    h = stable_hash(key)
    assert h == stable_hash(key)
    assert 0 <= h < 2**64
