"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp ref."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_index, from_edges
from repro.core.query import batched_query
from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.embedding_bag.ops import embedding_bag, embedding_lookup
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_decode.kernel import flash_decode_pallas
from repro.kernels.flash_decode.ops import decode_attention
from repro.kernels.flash_decode.ref import flash_decode_ref
from repro.kernels.segment_matmul.kernel import segment_matmul_pallas
from repro.kernels.segment_matmul.ref import segment_matmul_ref
from repro.kernels.spc_query.kernel import spc_query_pallas
from repro.kernels.spc_query.ops import index_query_batch
from repro.kernels.spc_query.ref import spc_query_ref

from tests.core.test_refimpl import PAPER_EDGES


def rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
class TestSpcQueryKernel:
    @pytest.mark.parametrize("b,l,block_b", [
        (4, 8, 128), (130, 16, 128), (256, 32, 256), (17, 128, 128),
    ])
    def test_sweep_vs_ref(self, b, l, block_b):
        r = rng(b * l)
        n_hubs = 50
        hub_s = jnp.asarray(np.sort(r.integers(0, n_hubs, (b, l))), jnp.int32)
        hub_t = jnp.asarray(np.sort(r.integers(0, n_hubs, (b, l))), jnp.int32)
        dist_s = jnp.asarray(r.integers(0, 12, (b, l)), jnp.int32)
        dist_t = jnp.asarray(r.integers(0, 12, (b, l)), jnp.int32)
        cnt_s = jnp.asarray(r.integers(1, 9, (b, l)), jnp.float32)
        cnt_t = jnp.asarray(r.integers(1, 9, (b, l)), jnp.float32)
        d_k, c_k = spc_query_pallas(hub_s, dist_s, cnt_s, hub_t, dist_t,
                                    cnt_t, block_b=block_b, interpret=True)
        d_r, c_r = spc_query_ref(hub_s, dist_s, cnt_s, hub_t, dist_t, cnt_t)
        np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
        np.testing.assert_allclose(np.asarray(c_k), np.asarray(c_r))

    def test_against_real_index(self):
        g = from_edges(12, PAPER_EDGES)
        idx = build_index(g, l_cap=8)
        s = jnp.asarray([4, 0, 0, 2, 11], jnp.int32)
        t = jnp.asarray([6, 9, 11, 8, 5], jnp.int32)
        d_k, c_k = index_query_batch(idx, s, t, interpret=True)
        d_r, c_r = batched_query(idx, s, t)
        assert c_k.dtype == jnp.int64  # exact contract of the wrapper
        np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
        np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))

    def test_counts_above_2_24_fall_back_to_int64(self):
        """Regression: fp32 kernel counts are exact only to 2^24; the
        wrapper must detect this from the kernel's own answer and serve
        the row from the int64 merge path instead of silently rounding."""
        from repro.core.labels import from_ref
        from repro.core.refimpl import RefSPCIndex
        from repro.kernels.spc_query.ops import EXACT_COUNT_MAX

        big = EXACT_COUNT_MAX + 1  # odd, not representable in fp32
        ref = RefSPCIndex(3)
        ref.labels[0] = [(0, 0, 1)]
        ref.labels[1] = [(0, 1, big), (1, 0, 1)]
        ref.labels[2] = [(0, 2, 7), (2, 0, 1)]
        idx = from_ref(ref, l_cap=4)
        d, c = index_query_batch(idx, jnp.asarray([0, 0]), jnp.asarray([1, 2]),
                                 interpret=True)
        assert c.dtype == jnp.int64
        assert (int(d[0]), int(c[0])) == (1, big)      # exact
        assert (int(d[1]), int(c[1])) == (2, 7)
        # the raw fp32 contract demonstrably rounds the same query
        _, c_raw = index_query_batch(idx, jnp.asarray([0]), jnp.asarray([1]),
                                     interpret=True, exact=False)
        assert c_raw.dtype == jnp.float32
        assert float(c_raw[0]) == EXACT_COUNT_MAX  # off by one: 2^24, not 2^24+1


# ---------------------------------------------------------------------------
class TestSegmentMatmul:
    @pytest.mark.parametrize("e,n,d,be,bn", [
        (100, 30, 16, 32, 16), (1000, 128, 64, 256, 128),
        (513, 65, 8, 128, 32), (64, 300, 4, 64, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep_vs_ref(self, e, n, d, be, bn, dtype):
        r = rng(e + n)
        vals = jnp.asarray(r.standard_normal((e, d)), dtype)
        dst = jnp.asarray(r.integers(0, n + 5, e), jnp.int32)  # incl. drops
        out_k = segment_matmul_pallas(vals, dst, n, block_e=be, block_n=bn,
                                      interpret=True)
        if dtype == jnp.float32:
            out_r = segment_matmul_ref(vals, dst, n)
            np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                       rtol=1e-6, atol=1e-6)
        else:
            # Kernel accumulates in f32 scratch (more accurate than a bf16
            # segment_sum); compare against the f32-accumulated truth
            # within one bf16 ulp.
            truth = segment_matmul_ref(vals.astype(jnp.float32), dst, n)
            np.testing.assert_allclose(np.asarray(out_k, np.float32),
                                       np.asarray(truth),
                                       rtol=1e-2, atol=1e-2)

    def test_matches_bfs_relaxation(self):
        """The kernel is the DSPC edge relaxation (counts as f32)."""
        g = from_edges(12, PAPER_EDGES)
        cnt = jnp.asarray(rng(3).integers(1, 5, 13), jnp.float32)
        frontier = jnp.asarray(rng(4).random(13) < 0.5)
        contrib = jnp.where(frontier[g.src], cnt[g.src], 0.0)[:, None]
        out_k = segment_matmul_pallas(contrib, g.dst, 13, block_e=16,
                                      block_n=8, interpret=True)
        out_r = jax.ops.segment_sum(contrib[:, 0], g.dst, num_segments=13)
        np.testing.assert_allclose(np.asarray(out_k[:, 0]), np.asarray(out_r))


# ---------------------------------------------------------------------------
class TestFlashDecode:
    @pytest.mark.parametrize("bh,s,d,bs", [
        (4, 64, 32, 16), (8, 1024, 128, 256), (3, 100, 64, 64),
        (16, 333, 16, 128),
    ])
    def test_sweep_vs_ref(self, bh, s, d, bs):
        r = rng(bh * s)
        q = jnp.asarray(r.standard_normal((bh, d)), jnp.float32)
        k = jnp.asarray(r.standard_normal((bh, s, d)), jnp.float32)
        v = jnp.asarray(r.standard_normal((bh, s, d)), jnp.float32)
        lengths = jnp.asarray(r.integers(1, s + 1, bh), jnp.int32)
        out_k = flash_decode_pallas(q, k, v, lengths, block_bh=4, block_s=bs,
                                    interpret=True)
        out_r = flash_decode_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_wrapper(self):
        r = rng(7)
        b, h, kvh, s, d = 2, 8, 2, 64, 32
        q = jnp.asarray(r.standard_normal((b, h, d)), jnp.float32)
        k = jnp.asarray(r.standard_normal((b, s, kvh, d)), jnp.float32)
        v = jnp.asarray(r.standard_normal((b, s, kvh, d)), jnp.float32)
        lengths = jnp.asarray([s, s // 2], jnp.int32)
        out_k = decode_attention(q, k, v, lengths, use_kernel=True,
                                 interpret=True, block_bh=4, block_s=32)
        out_r = decode_attention(q, k, v, lengths, use_kernel=False)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
class TestEmbeddingBag:
    @pytest.mark.parametrize("b,s,v,d", [
        (4, 3, 16, 128), (32, 20, 1000, 16), (7, 1, 64, 32),
    ])
    def test_sweep_vs_ref(self, b, s, v, d):
        r = rng(b + v)
        ids = jnp.asarray(r.integers(0, v, (b, s)), jnp.int32)
        table = jnp.asarray(r.standard_normal((v + 1, d)), jnp.float32)
        table = table.at[v].set(0.0)
        out_k = embedding_bag_pallas(ids, table, interpret=True)
        out_r = embedding_bag_ref(ids, table)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-6, atol=1e-6)

    def test_padding_and_mean(self):
        r = rng(11)
        v, d = 50, 8
        table = jnp.asarray(r.standard_normal((v, d)), jnp.float32)
        ids = jnp.asarray([[1, 2, -1], [3, -1, -1]], jnp.int32)
        ids = jnp.where(ids < 0, 99, ids)  # pad id
        out = embedding_bag(ids, table, mode="mean", pad_id=99,
                            use_kernel=True, interpret=True)
        exp0 = (np.asarray(table)[1] + np.asarray(table)[2]) / 2
        exp1 = np.asarray(table)[3]
        np.testing.assert_allclose(np.asarray(out[0]), exp0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out[1]), exp1, rtol=1e-6)

    def test_lookup(self):
        r = rng(13)
        table = jnp.asarray(r.standard_normal((10, 4)), jnp.float32)
        ids = jnp.asarray([[0, 9], [5, 10]], jnp.int32)
        out = embedding_lookup(ids, table, pad_id=10)
        np.testing.assert_allclose(np.asarray(out[0, 0]), np.asarray(table[0]))
        np.testing.assert_allclose(np.asarray(out[1, 1]), np.zeros(4))
