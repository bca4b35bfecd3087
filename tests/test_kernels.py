"""Pallas kernel correctness: shape/dtype sweeps + hypothesis vs ref oracles.

All kernels run in interpret mode on CPU (the kernel bodies execute in
Python), asserting allclose against the pure-jnp references in ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


class TestCodedMatvec:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("chunks,br,d,nvec", [
        (8, 8, 128, 1), (12, 16, 300, 3), (6, 32, 512, 8), (5, 8, 130, 2)])
    def test_sweep(self, dtype, chunks, br, d, nvec):
        a = _rand((chunks * br, d), dtype)
        x = _rand((d, nvec), dtype)
        ids = jnp.asarray(RNG.choice(chunks, size=max(2, chunks // 2),
                                     replace=False), jnp.int32)
        got = ops.coded_matvec(a, x, ids, br)
        want = ref.coded_matvec_ref(a, x, ids, br)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])

    def test_vector_input(self):
        a = _rand((64, 96), jnp.float32)
        x = _rand((96,), jnp.float32)
        ids = jnp.asarray([3, 0, 7], jnp.int32)
        got = ops.coded_matvec(a, x, ids, 8)
        want = ref.coded_matvec_ref(a, x[:, None], ids, 8)[:, :, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_work_scales_with_assignment(self):
        """Compacted output shape == #assigned blocks (the S²C² property)."""
        a = _rand((64, 128), jnp.float32)
        x = _rand((128, 1), jnp.float32)
        for nb in (1, 3, 8):
            ids = jnp.arange(nb, dtype=jnp.int32)
            out = ops.coded_matvec(a, x, ids, 8)
            assert out.shape == (nb, 8, 1)

    @given(st.integers(2, 10), st.integers(1, 4), st.data())
    @settings(max_examples=15, deadline=None)
    def test_property_random_ids(self, chunks, nvec, data):
        br, d = 8, 128
        a = _rand((chunks * br, d), jnp.float32)
        x = _rand((d, nvec), jnp.float32)
        nb = data.draw(st.integers(1, chunks))
        ids = jnp.asarray(
            data.draw(st.lists(st.integers(0, chunks - 1), min_size=nb,
                               max_size=nb)), jnp.int32)
        got = ops.coded_matvec(a, x, ids, br)
        want = ref.coded_matvec_ref(a, x, ids, br)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)


class TestChunkMatvec:
    """One engine chunk ``shard[r0:r0+rows] @ x``, walked in row blocks."""

    @pytest.mark.parametrize("shard_rows,r0,rows,nvec", [
        (64, 8, 40, None),                        # one block, padded to 40
        (96, 13, 5, 3),                           # unaligned start, tiny
        (2 * ops.MAX_BLOCK_ROWS + 300, 150,       # three blocks, the last
         2 * ops.MAX_BLOCK_ROWS + 100, 2),        # one mostly padding
        # a run of 8 chunks from chunk 2: blocks of 656 and 960, no padding
        (10 * 328, 2 * 328, 8 * 328, None),
        (10 * 480, 2 * 480, 8 * 480, 3),
    ])
    def test_matches_slice_product(self, shard_rows, r0, rows, nvec):
        d = 128
        shard = _rand((shard_rows, d), jnp.float32)
        x = _rand((d,) if nvec is None else (d, nvec), jnp.float32)
        got = ops.chunk_matvec(shard, x, r0, rows)
        want = np.asarray(shard, np.float64)[r0:r0 + rows] @ np.asarray(
            x, np.float64)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                                   atol=2e-4)

    @pytest.mark.parametrize("chunk_rows", [328, 480])
    def test_runs_are_walked_in_blocks_with_no_padded_rows(self, chunk_rows):
        """Every run length of 20 chunks a shard divides into whole blocks
        of at least MIN_SPLIT_ROWS rows; up to MAX_BLOCK_ROWS a call is
        one block of its rows rounded up to 8, as it always was."""
        from repro.cluster.worker import run_lengths
        for n in run_lengths(20):
            rows = n * chunk_rows
            block = ops.row_block(rows)
            assert rows % block == 0 and block % 8 == 0
            assert ops.MIN_SPLIT_ROWS <= block <= ops.MAX_BLOCK_ROWS
        for rows in range(1, ops.MAX_BLOCK_ROWS + 1):
            assert ops.row_block(rows) == -(-rows // 8) * 8

    def test_start_row_is_traced(self):
        """Every chunk of a shard reuses one compiled program."""
        shard = _rand((80, 128), jnp.float32)
        x = _rand((128,), jnp.float32)
        ops.chunk_matvec(shard, x, 0, 16)
        before = ops.chunk_matvec._cache_size()
        for r0 in (16, 32, 48, 64):
            ops.chunk_matvec(shard, x, r0, 16)
        assert ops.chunk_matvec._cache_size() == before


class TestMDSEncode:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n,k,rows,d", [
        (5, 3, 64, 128), (12, 10, 100, 260), (4, 4, 16, 640)])
    def test_sweep(self, dtype, n, k, rows, d):
        g = _rand((n, k), jnp.float32)
        blocks = _rand((k, rows, d), dtype)
        got = ops.mds_encode(g.astype(dtype), blocks)
        want = ref.mds_encode_ref(g.astype(dtype), blocks)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])


class TestMDSDecode:
    @pytest.mark.parametrize("chunks,k,m,r", [
        (4, 3, 5, 128), (6, 7, 10, 200), (1, 2, 2, 512)])
    def test_sweep(self, chunks, k, m, r):
        w = _rand((chunks, k, m), jnp.float32)
        y = _rand((chunks, m, r), jnp.float32)
        got = ops.mds_decode(w, y)
        want = ref.mds_decode_ref(w, y)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_end_to_end_with_code(self):
        """Kernel decode inverts kernel encode through a real MDS code."""
        from repro.core.coding import MDSCode
        code = MDSCode(n=6, k=4)
        blocks = _rand((4, 32, 64), jnp.float32)
        coded = ops.mds_encode(jnp.asarray(code.generator, jnp.float32),
                               blocks)
        workers = [5, 1, 2, 4]
        dm = jnp.asarray(code.decode_matrix(workers), jnp.float32)
        y = coded[jnp.asarray(workers)].reshape(1, 4, -1)
        got = ops.mds_decode(dm[None], y).reshape(4, 32, 64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(blocks),
                                   rtol=1e-3, atol=1e-3)


class TestLSTMCell:
    @pytest.mark.parametrize("b,i,h", [(1, 1, 4), (12, 1, 4), (100, 3, 8),
                                       (7, 2, 16)])
    def test_sweep(self, b, i, h):
        x = _rand((b, i), jnp.float32)
        hs = _rand((b, h), jnp.float32)
        cs = _rand((b, h), jnp.float32)
        wih = _rand((4 * h, i), jnp.float32)
        whh = _rand((4 * h, h), jnp.float32)
        bias = _rand((4 * h,), jnp.float32)
        gh, gc = ops.lstm_cell(x, hs, cs, wih, whh, bias)
        wh, wc = ref.lstm_cell_ref(x, hs, cs, wih, whh, bias)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(wh),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gc), np.asarray(wc),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_predictor_cell(self):
        """Kernel agrees with the predictor's reference LSTM cell."""
        from repro.core.predictor import LSTMParams, init_lstm, lstm_cell
        params = init_lstm(LSTMParams(), jax.random.PRNGKey(0))
        x = _rand((6, 1), jnp.float32)
        h = jnp.zeros((6, 4)); c = jnp.zeros((6, 4))
        wh, wc = lstm_cell(params, x, (h, c))
        gh, gc = ops.lstm_cell(x, h, c, params["w_ih"], params["w_hh"],
                               params["b"])
        np.testing.assert_allclose(np.asarray(gh), np.asarray(wh),
                                   rtol=1e-5, atol=1e-5)
