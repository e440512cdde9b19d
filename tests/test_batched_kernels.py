"""Parity sweeps for the batched server-plane kernels: interpret-mode Pallas
and the jit'd ops wrappers (under both REPRO_KERNELS settings) against the
pure-jnp oracles in ref.py — including ragged cluster sizes and the
single-member-cluster edge case."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.assign_lerp import assign_and_lerp
from repro.kernels.chi2_feedback import chi2_feedback_segmented
from repro.kernels.l1_pairwise import l1_distance_pairwise


# ------------------------------------------------------------- l1 pairwise
@pytest.mark.parametrize("m,c,n", [(1, 1, 1), (3, 5, 100), (9, 2, 700), (17, 9, 300), (8, 8, 8192)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l1_pairwise_matches_ref(m, c, n, dtype):
    xs = jax.random.normal(jax.random.PRNGKey(m * 13 + n), (m, n), dtype)
    cs = jax.random.normal(jax.random.PRNGKey(c * 7 + n), (c, n), dtype)
    got = np.asarray(l1_distance_pairwise(xs, cs, interpret=True))
    want = np.asarray(ref.l1_distance_pairwise_ref(xs, cs))
    np.testing.assert_allclose(got, want, rtol=3e-3 if dtype == jnp.bfloat16 else 1e-5)


def test_l1_pairwise_crosses_block_boundaries():
    xs = jax.random.normal(jax.random.PRNGKey(0), (5, 700))
    cs = jax.random.normal(jax.random.PRNGKey(1), (3, 700))
    got = np.asarray(
        l1_distance_pairwise(xs, cs, block_m=2, block_c=2, block_n=128, interpret=True)
    )
    want = np.asarray(ref.l1_distance_pairwise_ref(xs, cs))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_l1_pairwise_self_diagonal_is_zero():
    vs = jax.random.normal(jax.random.PRNGKey(2), (6, 256))
    d = np.asarray(l1_distance_pairwise(vs, vs, interpret=True))
    np.testing.assert_allclose(np.diag(d), 0.0, atol=1e-5)
    np.testing.assert_allclose(d, d.T, rtol=1e-5, atol=1e-5)

    # agreement with the one-vs-many streaming kernel, row by row
    from repro.kernels.l1_distance import l1_distance

    for i in range(6):
        np.testing.assert_allclose(
            d[i], np.asarray(l1_distance(vs[i], vs, interpret=True)), rtol=1e-5, atol=1e-5
        )


# ------------------------------------------------------------ assign + lerp
@pytest.mark.parametrize("c,n", [(1, 100), (5, 300), (8, 4096), (3, 70000)])
@pytest.mark.parametrize("beta", [0.0, 0.25, 1.0])
def test_assign_and_lerp_matches_ref(c, n, beta):
    u = jax.random.normal(jax.random.PRNGKey(n + c), (n,))
    cs = jax.random.normal(jax.random.PRNGKey(n - c), (c, n))
    d, i, b = assign_and_lerp(u, cs, beta, interpret=True)
    dr, ir, br = ref.assign_and_lerp_ref(u, cs, beta)
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), rtol=1e-5)
    assert int(i) == int(ir)
    np.testing.assert_allclose(np.asarray(b), np.asarray(br), rtol=1e-5, atol=1e-6)


def test_assign_and_lerp_blends_only_the_argmin_center():
    u = jnp.full((256,), 2.0)
    cs = jnp.stack([jnp.zeros(256), jnp.full((256,), 1.9), jnp.full((256,), 100.0)])
    d, i, b = assign_and_lerp(u, cs, 0.5, interpret=True)
    assert int(i) == 1
    np.testing.assert_allclose(np.asarray(b), 0.5 * 1.9 + 0.5 * 2.0, rtol=1e-6)
    assert float(d[0]) == pytest.approx(2.0 * 256, rel=1e-6)


# --------------------------------------------------------- segmented chi2
def _feedback_batch(m, j, seed=0):
    k = jax.random.PRNGKey(seed)
    f_pred = jax.random.uniform(k, (m, j)) * 100
    f_true = jax.random.uniform(jax.random.PRNGKey(seed + 1), (m, j)) * 100 + 1.0
    s_soft = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(seed + 2), (m, j)), axis=-1)
    return f_pred, f_true, s_soft


@pytest.mark.parametrize(
    "sizes", [[1], [3, 1, 7], [5, 5], [2, 1, 1, 9, 4]],
    ids=["single-member", "ragged", "even", "very-ragged"],
)
def test_chi2_segmented_matches_ref(sizes):
    m, s = sum(sizes), len(sizes)
    f_pred, f_true, s_soft = _feedback_batch(m, 6)
    seg_ids = jnp.asarray(np.repeat(np.arange(s), sizes), jnp.int32)
    onehot = (seg_ids[:, None] == jnp.arange(s)[None, :]).astype(jnp.float32)
    g, seg_sum = chi2_feedback_segmented(f_pred, f_true, s_soft, onehot, interpret=True)
    g_ref, seg_ref = ref.chi2_feedback_segmented_ref(f_pred, f_true, s_soft, onehot)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(seg_sum), np.asarray(seg_ref), rtol=2e-5, atol=1e-6)
    # segment sums really are the per-cluster totals of g
    want = np.asarray([np.asarray(g_ref)[seg_ids == i].sum() for i in range(s)])
    np.testing.assert_allclose(np.asarray(seg_sum), want, rtol=1e-4, atol=1e-5)


def test_chi2_segmented_crosses_m_blocks():
    m, s = 600, 3  # crosses the 256-row block boundary
    f_pred, f_true, s_soft = _feedback_batch(m, 4, seed=9)
    seg_ids = jnp.asarray(np.arange(m) % s, jnp.int32)
    onehot = (seg_ids[:, None] == jnp.arange(s)[None, :]).astype(jnp.float32)
    g, seg_sum = chi2_feedback_segmented(f_pred, f_true, s_soft, onehot, interpret=True)
    g_ref, seg_ref = ref.chi2_feedback_segmented_ref(f_pred, f_true, s_soft, onehot)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(seg_sum), np.asarray(seg_ref), rtol=1e-4, atol=1e-4)


# ------------------------------------------- ops wrappers, both backends
@pytest.fixture(params=["ref", "pallas"])
def force_backend(request, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", request.param)
    return request.param


def test_ops_l1_pairwise_both_backends(force_backend):
    xs = jax.random.normal(jax.random.PRNGKey(3), (4, 500))
    cs = jax.random.normal(jax.random.PRNGKey(4), (6, 500))
    got = np.asarray(ops.l1_distance_pairwise(xs, cs))
    np.testing.assert_allclose(got, np.asarray(ref.l1_distance_pairwise_ref(xs, cs)), rtol=1e-5)


def test_ops_assign_and_lerp_both_backends(force_backend):
    u = jax.random.normal(jax.random.PRNGKey(5), (300,))
    cs = jax.random.normal(jax.random.PRNGKey(6), (4, 300))
    d, i, b = ops.assign_and_lerp(u, cs, 0.25)
    dr, ir, br = ref.assign_and_lerp_ref(u, cs, 0.25)
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), rtol=1e-5)
    assert int(i) == int(ir)
    np.testing.assert_allclose(np.asarray(b), np.asarray(br), rtol=1e-5, atol=1e-6)


def test_ops_chi2_feedback_all_both_backends(force_backend):
    sizes = [4, 1, 6]
    m, s = sum(sizes), len(sizes)
    f_pred, f_true, s_soft = _feedback_batch(m, 5, seed=20)
    seg_ids = jnp.asarray(np.repeat(np.arange(s), sizes), jnp.int32)
    g, seg_sum = ops.chi2_feedback_all(f_pred, f_true, s_soft, seg_ids, num_segments=s)
    onehot = (seg_ids[:, None] == jnp.arange(s)[None, :]).astype(jnp.float32)
    g_ref, seg_ref = ref.chi2_feedback_segmented_ref(f_pred, f_true, s_soft, onehot)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(seg_sum), np.asarray(seg_ref), rtol=1e-4, atol=1e-5)
