"""Packed-bit deployment path: pack/unpack roundtrips, bit-exact parity
of the XOR+popcount kernel with the float kernel and the jnp argmax
reference, the kernel-grid == IMC-cycle-model contract, and the
deploy(packed=True) serving artifact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import imc
from repro.kernels import ops, ref
from repro.kernels.am_search import imc_cycles_for as search_cycles
from repro.kernels.am_search_packed import imc_cycles_for as packed_cycles

RNG = np.random.default_rng(7)


def bipolar(shape, dtype=np.float32):
    return jnp.asarray(RNG.choice([-1.0, 1.0], size=shape).astype(dtype))


class TestPackRows:
    """pack_rows: the ragged-D packer (non-multiple-of-8 tails)."""

    @pytest.mark.parametrize("r,c", [
        (1, 1), (3, 7), (5, 8), (4, 9), (128, 128), (2, 130),
        (17, 617), (1, 1023),
    ])
    def test_roundtrip(self, r, c):
        x = bipolar((r, c))
        p = ops.pack_rows(x)
        assert p.dtype == jnp.uint8 and p.shape == (r, -(-c // 8))
        np.testing.assert_array_equal(np.asarray(p),
                                      np.asarray(ref.pack_rows(x)))
        # Valid bits roundtrip through the full-width unpacker...
        u = ops.unpack_bits(p)[:, :c]
        np.testing.assert_array_equal(np.asarray(u), np.asarray(x))
        # ...and tail bits are packed as 0 (they must XOR-cancel).
        tail = np.asarray(ops.unpack_bits(p))[:, c:]
        assert np.all(tail == -1.0)

    def test_one_bit_per_cell(self):
        x = bipolar((128, 128))
        p = ops.pack_rows(x)
        assert p.size * 8 == x.size


class TestPackedSearchParity:
    """am_search_packed == am_search == jnp.argmax, bit for bit."""

    @pytest.mark.parametrize("b,d,c", [
        (1, 128, 128), (8, 128, 128), (3, 256, 64), (5, 512, 300),
        (2, 130, 257), (7, 120, 26), (300, 64, 26), (4, 9, 3),
    ])
    @pytest.mark.parametrize("mode", ["popcount", "unpack"])
    def test_matches_unpacked_and_reference(self, b, d, c, mode):
        q = bipolar((b, d))
        am = bipolar((c, d))
        qp = ops.pack_rows(q)
        apt = ops.pack_rows(am).T

        gi, gs = ops.am_search_packed(qp, apt, n_dims=d, mode=mode)
        ui, us = ops.am_search(q, am)
        wi, ws = ref.am_search(q, am.T)

        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ui))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(us))
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))

    def test_packed_oracle_matches_reference(self):
        q, am = bipolar((6, 200)), bipolar((40, 200))
        ri, rs = ref.am_search_packed(
            ref.pack_rows(q), ref.pack_rows(am).T, 200)
        wi, ws = ref.am_search(q, am.T)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(ws))

    @pytest.mark.parametrize("mode", ["popcount", "unpack"])
    def test_tie_breaking_first_wins(self, mode):
        # Duplicate centroids force ties; argmax must take the first —
        # including across C-tile boundaries (c=150 spans two tiles).
        q = bipolar((4, 128))
        row = bipolar((1, 128))
        am = jnp.concatenate([row] * 150, axis=0)
        gi, _ = ops.am_search_packed(
            ops.pack_rows(q), ops.pack_rows(am).T, n_dims=128, mode=mode)
        assert np.all(np.asarray(gi) == 0)

    def test_hamming_identity(self):
        # sim = D - 2*hamming on the packed bits.
        q, am = bipolar((5, 96)), bipolar((12, 96))
        ham = np.asarray(ref.hamming_distances(
            ref.pack_rows(q), ref.pack_rows(am).T))
        sims = np.asarray(q) @ np.asarray(am).T
        np.testing.assert_array_equal(96 - 2 * ham, sims)

    def test_rejects_bad_args(self):
        qp = ops.pack_rows(bipolar((2, 64)))
        apt = ops.pack_rows(bipolar((8, 64))).T
        with pytest.raises(ValueError):
            ops.am_search_packed(qp, apt, n_dims=64, mode="bogus")
        with pytest.raises(ValueError):
            ops.am_search_packed(qp, apt, n_dims=32)  # Dp mismatch


class TestPackedGridContract:
    """Kernel geometry == IMC cost model, packed == unpacked."""

    def test_one_shot_for_paper_flagship(self):
        # The paper's 128x128 flagship: the packed search is ONE IMC
        # array cycle, as am_search.py promises. The count is a function
        # of shapes; the Pallas grid may span up to 8 arrays along D per
        # step (a lane-aligned 128-byte block), so it is not the grid.
        apt_shape = (128 // 8, 128)  # (Dp, C) of the packed AM
        assert packed_cycles(apt_shape) == 1
        assert packed_cycles(apt_shape) == \
            imc.map_memhd(128, 128, imc.ImcArrayConfig()).cycles

    @pytest.mark.parametrize("d,c", [
        (128, 128), (256, 256), (512, 128), (1024, 1024), (130, 257),
        (617, 26),
    ])
    def test_matches_unpacked_and_cost_model(self, d, c):
        apt_shape = (-(-d // 8), c)
        assert packed_cycles(apt_shape) == search_cycles((d, c))
        assert packed_cycles(apt_shape) == \
            imc.map_memhd(d, c, imc.ImcArrayConfig()).cycles


class TestDeployedModel:
    @pytest.fixture(scope="class")
    def trained(self, small_hdc_data):
        from repro.core import EncoderConfig, MemhdConfig, MemhdModel
        ds = small_hdc_data
        enc = EncoderConfig(kind="projection", features=ds.features,
                            dim=128)
        amc = MemhdConfig(dim=128, columns=64, classes=ds.classes,
                          epochs=2, kmeans_iters=5)
        m = MemhdModel.create(jax.random.key(0), enc, amc)
        m, _ = m.fit(jax.random.key(1), ds.train_x, ds.train_y)
        return ds, m

    def test_packed_deploy_bit_exact_and_8x_smaller(self, trained):
        ds, m = trained
        dep_p = m.deploy(packed=True)
        dep_u = m.deploy(packed=False)
        pp = np.asarray(dep_p.predict(ds.test_x))
        np.testing.assert_array_equal(pp, np.asarray(dep_u.predict(
            ds.test_x)))
        np.testing.assert_array_equal(pp, np.asarray(m.predict(
            ds.test_x)))
        assert dep_p.score(ds.test_x, ds.test_y) == \
            m.score(ds.test_x, ds.test_y)
        # Resident AM: 1 bit/cell vs 1 byte/cell vs float32 cells.
        assert dep_p.resident_am_bytes * 8 == 64 * 128
        assert dep_p.am_memory_ratio == 8.0
        assert dep_u.resident_am_bytes == 4 * dep_p.am_memory_ratio * \
            dep_p.resident_am_bytes

    def test_unpack_mode_matches(self, trained):
        ds, m = trained
        pred_pop = m.deploy(packed=True, mode="popcount").predict(
            ds.test_x[:32])
        pred_unp = m.deploy(packed=True, mode="unpack").predict(
            ds.test_x[:32])
        np.testing.assert_array_equal(np.asarray(pred_pop),
                                      np.asarray(pred_unp))

    def test_deployed_is_a_pytree(self, trained):
        _, m = trained
        dep = m.deploy(packed=True)
        leaves = jax.tree_util.tree_leaves(dep)
        assert any(leaf.dtype == jnp.uint8 for leaf in leaves)
        rebuilt = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(dep), leaves)
        assert rebuilt.packed and rebuilt.am_cfg == dep.am_cfg

    def test_packed_predict_helper(self, trained):
        ds, m = trained
        from repro.core import am as am_lib
        q = m.encode_query(ds.test_x[:20])
        apt = am_lib.pack_am(m.am_state["binary"])
        pred = am_lib.packed_predict(
            apt, m.am_state["centroid_class"], q, m.am_cfg.dim)
        np.testing.assert_array_equal(
            np.asarray(pred), np.asarray(m.predict(ds.test_x[:20])))
        assert am_lib.packed_am_bytes(m.am_cfg.dim, m.am_cfg.columns) \
            == apt.size


class TestServeBatching:
    """serve_memhd request batching: tile padding, no request splits."""

    def _reqs(self, sizes):
        from repro.launch.serve_memhd import Request
        return [Request(rid=i, feats=np.zeros((n, 4), np.float32))
                for i, n in enumerate(sizes)]

    def test_greedy_batching_never_splits(self):
        from repro.launch.serve_memhd import make_batches
        batches = make_batches(self._reqs([10, 10, 10, 50, 100, 3]), 64)
        assert [sorted(r.rid for r in b) for b in batches] == \
            [[0, 1, 2], [3], [4], [5]]
        assert all(sum(r.size for r in b) <= 64
                   for b in batches if len(b) > 1)

    def test_oversize_request_gets_own_batch(self):
        from repro.launch.serve_memhd import make_batches
        batches = make_batches(self._reqs([200]), 64)
        assert len(batches) == 1 and batches[0][0].size == 200

    def test_pad_to_multiple(self):
        from repro.launch.serve_memhd import pad_to_multiple
        x = np.ones((13, 4), np.float32)
        padded, n = pad_to_multiple(x, 8)
        assert padded.shape == (16, 4) and n == 13
        assert np.all(padded[13:] == 0)
        same, n2 = pad_to_multiple(np.ones((16, 4), np.float32), 8)
        assert same.shape == (16, 4) and n2 == 16

    def test_serve_batches_routes_responses(self, small_hdc_data):
        from repro.core import EncoderConfig, MemhdConfig, MemhdModel
        from repro.launch.serve_memhd import (Request, serve_batches,
                                              synthetic_requests)
        ds = small_hdc_data
        enc = EncoderConfig(kind="projection", features=ds.features,
                            dim=128)
        amc = MemhdConfig(dim=128, columns=32, classes=ds.classes,
                          epochs=1, kmeans_iters=3)
        m = MemhdModel.create(jax.random.key(0), enc, amc)
        m, _ = m.fit(jax.random.key(1), ds.train_x, ds.train_y)
        dep = m.deploy(packed=True)

        feats = np.asarray(ds.test_x)
        reqs = synthetic_requests(feats, n_requests=9, max_size=11,
                                  seed=3)
        responses, stats = serve_batches(dep, reqs, max_batch=32)
        assert stats["rows_real"] == sum(r.size for r in reqs)
        assert stats["rows_padded"] % 8 == 0
        for r in reqs:
            want = np.asarray(dep.predict(r.feats))
            np.testing.assert_array_equal(responses[r.rid], want)
