"""segment ops / EmbeddingBag / sampler / packing unit + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.graph import batching, sampler, segment_ops as so


def test_segment_softmax_normalizes():
    logits = jnp.array([1.0, 2.0, 3.0, -1.0, 0.5])
    seg = jnp.array([0, 0, 1, 1, 1])
    p = so.segment_softmax(logits, seg, 3)
    np.testing.assert_allclose(float(p[0] + p[1]), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(p[2] + p[3] + p[4]), 1.0, rtol=1e-6)


def test_segment_mean_std():
    x = jnp.array([[1.0], [3.0], [10.0]])
    seg = jnp.array([0, 0, 1])
    m = so.segment_mean(x, seg, 2)
    np.testing.assert_allclose(np.asarray(m), [[2.0], [10.0]], rtol=1e-6)
    s = so.segment_std(x, seg, 2)
    np.testing.assert_allclose(float(s[0, 0]), 1.0, rtol=1e-3)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 5), st.integers(1, 7), st.integers(2, 9),
       st.sampled_from(["sum", "mean", "max"]))
def test_embedding_bag_vs_manual(b, l, v, mode):
    rng = np.random.default_rng(b * 100 + l * 10 + v)
    table = jnp.asarray(rng.normal(size=(v, 3)).astype(np.float32))
    ids = rng.integers(-1, v, (b, l))  # -1 = padding
    out = so.embedding_bag(table, jnp.asarray(ids), mode=mode)
    for i in range(b):
        rows = [np.asarray(table)[j] for j in ids[i] if j >= 0]
        if not rows:
            want = np.zeros(3)
        elif mode == "sum":
            want = np.sum(rows, axis=0)
        elif mode == "mean":
            want = np.mean(rows, axis=0)
        else:
            want = np.max(rows, axis=0)
        np.testing.assert_allclose(np.asarray(out)[i], want, rtol=1e-5,
                                   atol=1e-6)


def test_embedding_bag_offsets_mode():
    table = jnp.eye(4, dtype=jnp.float32)
    ids = jnp.array([0, 1, 2, 3, 3], jnp.int32)
    offsets = jnp.array([0, 2, 4], jnp.int32)  # bags: [0,1], [2,3], [3]
    out = so.embedding_bag(table, ids, offsets=offsets, mode="sum")
    np.testing.assert_allclose(np.asarray(out),
                               [[1, 1, 0, 0], [0, 0, 1, 1], [0, 0, 0, 1]])


def test_embedding_bag_grad_flows():
    table = jnp.ones((5, 2), jnp.float32)
    ids = jnp.array([[0, 1], [2, -1]], jnp.int32)

    def loss(t):
        return jnp.sum(so.embedding_bag(t, ids) ** 2)

    g = jax.grad(loss)(table)
    assert np.asarray(g)[3].sum() == 0  # untouched row
    assert np.asarray(g)[0].sum() != 0


def test_coo_spmm_matches_dense():
    rng = np.random.default_rng(0)
    n, e = 6, 20
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = rng.normal(size=e).astype(np.float32)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    a = np.zeros((n, n), np.float32)
    for s, d, ww in zip(src, dst, w):
        a[d, s] += ww
    got = so.coo_spmm(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
                      jnp.asarray(x), n)
    np.testing.assert_allclose(np.asarray(got), a @ x, rtol=1e-4, atol=1e-5)


def test_sampler_shapes_and_determinism():
    csr = sampler.make_synthetic_csr(200, 8, seed=1)
    seeds = jnp.arange(16, dtype=jnp.int32)
    key = jax.random.PRNGKey(0)
    blocks, inputs = sampler.sample_blocks(csr, seeds, [15, 10], key)
    assert blocks[-1].src.shape == (16 * 15,)      # innermost (seed) layer
    assert blocks[0].src.shape == (16 * 15 * 10,)  # widest layer
    assert inputs.shape == (16 * 15 * 10,)
    blocks2, inputs2 = sampler.sample_blocks(csr, seeds, [15, 10], key)
    np.testing.assert_array_equal(np.asarray(inputs), np.asarray(inputs2))


def test_sampler_isolated_nodes_self_loop():
    # node 3 has no out-edges
    csr = sampler.build_csr(np.array([0, 1]), np.array([1, 2]), 4)
    blk, nxt = sampler.sample_block(csr, jnp.array([3], jnp.int32), 4,
                                    jax.random.PRNGKey(0))
    assert np.asarray(blk.src).tolist() == [3, 3, 3, 3]


def test_pack_dense_batch():
    g = batching.pack_dense_batch(4, 5, 8, seed=0)
    assert g.src.shape == (4 * 8,)
    assert g.node_mask.sum() == 4 * 5
    # edges stay within their own graph
    gid_src = np.asarray(g.graph_id)[np.asarray(g.src)]
    gid_dst = np.asarray(g.graph_id)[np.asarray(g.dst)]
    m = np.asarray(g.edge_mask)
    np.testing.assert_array_equal(gid_src[m], gid_dst[m])
