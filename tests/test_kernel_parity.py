"""Cross-kernel differential harness: every Pallas kernel vs its oracle.

One shared geometry grid — including non-multiple-of-128 D/C/f and
batch-1 edge cases — drives every kernel in ``repro.kernels`` against
its pure-jnp ``ref`` oracle. Each per-kernel suite elsewhere tests its
own corner semantics; this file is the drift gate: a change to any
kernel, oracle, or the shared padding/tiling conventions must keep the
whole matrix exactly in agreement (bipolar operands make every result
integer-valued, so all assertions are bit-exact). CI runs exactly this
file as a dedicated step so oracle drift fails fast.

The packed paths additionally get hypothesis-generated geometries and
bit patterns (pack/unpack roundtrips and search parity over random
shapes), since byte-boundary bugs live in shapes nobody writes by hand.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoding
from repro.core.types import EncoderConfig, ImcArrayConfig, ImcSimConfig
from repro.kernels import ops, ref

# Shared geometry grid: (batch, features, dim, columns). Covers the
# paper's flagship points, ragged everything, and batch-1 serving.
GEOMS = [
    (1, 16, 128, 128),    # batch-1, flagship 128x128 AM
    (8, 784, 128, 128),   # MNIST paper point
    (3, 100, 130, 257),   # D and C just over a tile boundary
    (5, 617, 512, 300),   # ISOLET f, ragged C
    (2, 64, 120, 26),     # D and C under one tile
    (1, 9, 9, 3),         # tiny batch-1 edge (sub-byte D)
]


def geom_rng(*key):
    """Per-test RNG seeded by the test's own geometry (plus a salt per
    call site), so inputs don't depend on which other tests ran first —
    any failure reproduces under ``-k`` selection."""
    return np.random.default_rng([1234, *key])


def bipolar(rng, shape):
    return jnp.asarray(rng.choice([-1.0, 1.0], size=shape)
                       .astype(np.float32))


def feats_mat(rng, b, f):
    return jnp.asarray(rng.random((b, f), dtype=np.float32))


def sparse_parity(rng, d, assign, g, short, ks, block_b=None):
    """Sparse fine pass over ``build_layout(AM, assign, g)``: the Pallas
    kernel (interpret mode off-TPU) against the XLA gather + oracle
    path, bit for bit, at every k in ``ks``. Returns the layout, the
    expanded (B, T) tile table and the kernel's last (idx, sims)."""
    from repro.deploy import hierarchical as hier
    from repro.kernels.am_search_sparse import expand_shortlist_tiles
    c, b = assign.shape[0], short.shape[0]
    q, am = bipolar(rng, (b, d)), bipolar(rng, (c, d))
    qp = ops.pack_rows(q)
    layout = hier.build_layout(np.asarray(ops.pack_rows(am).T),
                               assign.astype(np.int32), g)
    args = (qp, jnp.asarray(layout.slab), jnp.asarray(layout.col_ids),
            jnp.asarray(short.astype(np.int32)),
            jnp.asarray(layout.tile_start), jnp.asarray(layout.tile_count))
    for k in ks:
        kw = dict(n_dims=d, k=k, max_tiles=layout.max_tiles)
        gi, gs = ops.am_search_sparse(*args, use_kernel=True,
                                      block_b=block_b, **kw)
        wi, ws = ops.am_search_sparse(*args, use_kernel=False, **kw)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
    table = np.asarray(expand_shortlist_tiles(
        args[3], args[4], args[5], max_tiles=layout.max_tiles,
        null_tile=layout.null_tile))
    return layout, table, (np.asarray(gi), np.asarray(gs))


@pytest.mark.parametrize("b,f,d,c", GEOMS)
class TestKernelOracleParity:
    """The differential sweep proper: kernel == oracle, bit for bit."""

    def test_binary_mvm(self, b, f, d, c):
        rng = geom_rng(b, f, d, 0)
        x = bipolar(rng, (b, f))  # bipolar x: integer-exact accumulation
        w = bipolar(rng, (f, d))
        np.testing.assert_array_equal(
            np.asarray(ops.encode_mvm(x, w)),
            np.asarray(ref.binary_mvm(x, w)))
        del c

    def test_am_search(self, b, f, d, c):
        rng = geom_rng(b, d, c, 1)
        q, am = bipolar(rng, (b, d)), bipolar(rng, (c, d))
        gi, gs = ops.am_search(q, am)
        wi, ws = ref.am_search(q, am.T)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
        del f

    @pytest.mark.parametrize("mode", ["popcount", "unpack"])
    def test_am_search_packed(self, b, f, d, c, mode):
        rng = geom_rng(b, d, c, 2)
        q, am = bipolar(rng, (b, d)), bipolar(rng, (c, d))
        qp = ops.pack_rows(q)
        apt = ops.pack_rows(am).T
        gi, gs = ops.am_search_packed(qp, apt, n_dims=d, mode=mode)
        wi, ws = ref.am_search_packed(qp, apt, d)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
        del f

    @pytest.mark.parametrize("adc_bits,rows,cols,with_offsets", [
        (16, 128, 128, False),   # exact-parity regime
        (6, 128, 128, False),    # lossy ADC: still kernel == oracle
        (8, 96, 80, True),       # ragged array geometry + tile drift
    ])
    def test_am_search_imc(self, b, f, d, c, adc_bits, rows, cols,
                           with_offsets):
        rng = geom_rng(b, d, c, adc_bits, rows, cols)
        q, am = bipolar(rng, (b, d)), bipolar(rng, (c, d))
        sim = ImcSimConfig(arr=ImcArrayConfig(rows=rows, cols=cols),
                           adc_bits=adc_bits)
        offsets = None
        if with_offsets:
            offsets = jnp.asarray(rng.normal(
                0, 0.3, (-(-d // rows), -(-c // cols))).astype(np.float32))
        gi, gs = ops.am_search_imc(q, am, sim=sim, offsets=offsets)
        wi, ws = ref.am_search_imc(
            q, am.T, tile_rows=rows, tile_cols=cols, adc_bits=adc_bits,
            adc_clip=sim.clip, offsets=offsets)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
        del f

    @pytest.mark.parametrize("cell_bits,with_offsets", [
        (2, False),            # ternary codes, pure code-domain readout
        (4, True),             # int4 + per-tile readout drift
    ])
    def test_am_search_multibit(self, b, f, d, c, cell_bits,
                                with_offsets):
        rng = geom_rng(b, d, c, 4, cell_bits)
        qmax = 2 ** (cell_bits - 1) - 1
        q = bipolar(rng, (b, d))
        codes = rng.integers(-qmax, qmax + 1, size=(c, d))
        planes = ref.pack_planes(jnp.asarray(codes + qmax), cell_bits)
        offsets = None
        if with_offsets:
            offsets = jnp.asarray(rng.normal(
                0, 0.3, (-(-d // 128), -(-c // 128))).astype(np.float32))
        gi, gs = ops.am_search_multibit(q, planes, offsets=offsets)
        wi, ws = ref.am_search_multibit(q, planes, cell_bits=cell_bits,
                                        offsets=offsets)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
        # Drift-free wide-ADC readout is the exact integer code MVM.
        if not with_offsets:
            exact = q @ jnp.asarray(codes, jnp.float32).T
            np.testing.assert_array_equal(
                np.asarray(gs), np.asarray(exact.max(axis=1)))
        del f

    def test_qail_update(self, b, f, d, c):
        k = max(2, c // 3)
        rng = geom_rng(b, d, c, 3)
        q = bipolar(rng, (b, d))
        upd = bipolar(rng, (b, d))  # update_with="binary": integer-exact
        am_t = bipolar(rng, (c, d)).T
        owners = jnp.asarray(rng.integers(0, k, size=(c,)), jnp.int32)
        # Every class needs a centroid for Eq. (5) to have a target.
        owners = owners.at[:k].set(jnp.arange(k, dtype=jnp.int32))
        mask = jnp.asarray((rng.random(b) < 0.8).astype(np.float32))
        if b > 1:  # keep at least one padded row in the sweep
            mask = mask.at[-1].set(0.0)
        labels = jnp.where(
            mask > 0,
            jnp.asarray(rng.integers(0, k, size=(b,)), jnp.int32), -1)
        gd, gm = ops.qail_update(q, upd, am_t, owners, labels, mask,
                                 lr=0.5)
        wd, wm = ref.qail_update_delta(q, upd, am_t, owners, labels,
                                       mask, 0.5)
        np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))
        np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))
        del f

    def test_am_shortlist(self, b, f, d, c):
        # The AM rows play the G super-centroids; sweep S from 1 to
        # the full (ragged) column count.
        rng = geom_rng(b, d, c, 6)
        q, supers = bipolar(rng, (b, d)), bipolar(rng, (c, d))
        qp = ops.pack_rows(q)
        spt = ops.pack_rows(supers).T
        for s in sorted({1, min(3, c), c}):
            gi, gs = ops.am_shortlist(qp, spt, n_dims=d, s=s,
                                      use_kernel=True)
            wi, ws = ref.am_shortlist(qp, spt, d, s)
            np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
            np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
        del f

    def test_am_search_sparse(self, b, f, d, c):
        # Random cluster layout over the ragged C; kernel path vs the
        # gather + ref-oracle path, including k > candidate count.
        rng = geom_rng(b, d, c, 7)
        g = max(1, c // 3)
        assign = rng.integers(0, g, size=c).astype(np.int32)
        s = min(2, g)
        short = np.stack([rng.permutation(g)[:s] for _ in range(b)])
        sparse_parity(rng, d, assign, g, short,
                      ks=(1, min(3, c), c + 2))  # c + 2: exhausted slots
        del f

    def test_encode_fused(self, b, f, d, c):
        rng = geom_rng(b, f, d, 4)
        x, w = feats_mat(rng, b, f), bipolar(rng, (f, d))
        got = ops.encode_pack(x, w)
        want = ref.encode_pack(x, w)
        assert got.dtype == jnp.uint8 and got.shape == (b, -(-d // 8))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        del c

    @pytest.mark.parametrize("mode", ["popcount", "unpack"])
    def test_fused_chain_matches_staged(self, b, f, d, c, mode):
        """predict_from_features == encode_query -> pack -> search,
        bit-exact including tie resolution (idx asserted, not just the
        class)."""
        rng = geom_rng(b, f, d, c, 5)
        x, w = feats_mat(rng, b, f), bipolar(rng, (f, d))
        am = bipolar(rng, (c, d))
        apt = ops.pack_rows(am).T
        owners = jnp.asarray(rng.integers(0, 10, size=(c,)), jnp.int32)

        # Staged chain, stage by stage (the pre-fusion serving path).
        h = jnp.dot(x, w)
        q = encoding.binarize_query(h)
        qp = ops.pack_rows(q)
        si, ss = ops.am_search_packed(qp, apt, n_dims=d, mode=mode)

        fi, fs = ops.search_from_features(x, w, apt, mode=mode)
        np.testing.assert_array_equal(np.asarray(fi), np.asarray(si))
        np.testing.assert_array_equal(np.asarray(fs), np.asarray(ss))
        pred = ops.predict_from_features(x, w, apt, owners, mode=mode)
        np.testing.assert_array_equal(np.asarray(pred),
                                      np.asarray(owners)[np.asarray(si)])


class TestEncodeFusedSemantics:
    """Fused-encoder corners the sweep can't hit."""

    def test_tail_bits_are_zero(self):
        # D=9 -> 2 bytes; the 7 tail bits must pack as 0 so they
        # XOR-cancel against the identically padded AM.
        rng = geom_rng(4, 16, 9, 6)
        x, w = feats_mat(rng, 4, 16), bipolar(rng, (16, 9))
        p = np.asarray(ops.encode_pack(x, w))
        assert np.all(p[:, 1] < 2)  # only bit 0 of byte 1 may be set

    def test_sign_zero_packs_as_one(self):
        # H == 0 rows: binarize_query maps sign(0) -> +1 -> bit 1.
        x = jnp.zeros((2, 8), jnp.float32)
        w = bipolar(geom_rng(2, 8, 16, 7), (8, 16))
        p = np.asarray(ops.encode_pack(x, w))
        assert np.all(p == 0xFF)

    def test_cycle_model_matches_mvm(self):
        from repro.core import imc
        from repro.kernels.binary_mvm import imc_cycles_for as mvm_cycles
        from repro.kernels.encode_fused import imc_cycles_for
        assert imc_cycles_for((8, 784), (784, 1024)) == \
            mvm_cycles((8, 784), (784, 1024))
        assert imc_cycles_for((8, 784), (784, 1024)) == \
            imc.map_basic(784, 1024, imc.ImcArrayConfig()).cycles


class TestEncoderChunkInvariance:
    """encode_id_level: H must not depend on the feature chunking —
    padded feature columns gather a neutral (masked-to-zero) level, so
    any chunk size gives the identical (exact, +-1-integer) H."""

    @pytest.mark.parametrize("f,chunk", [
        (100, 128), (100, 32), (100, 7), (128, 128), (130, 128),
    ])
    def test_chunk_size_invariance(self, f, chunk):
        cfg = EncoderConfig(kind="id_level", features=f, dim=64,
                            levels=8)
        params = encoding.init_id_level(jax.random.key(0), cfg)
        x = jnp.asarray(geom_rng(f, chunk, 8).random(
            (5, f), dtype=np.float32))
        base = encoding.encode_id_level(params, x, chunk=f)  # no pad
        got = encoding.encode_id_level(params, x, chunk=chunk)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))

    def test_padded_columns_are_neutral_even_for_nonfinite_levels(self):
        # The gather itself is masked: a poisoned lvls[0] must not leak
        # through the padded columns (0 * nan == nan would).
        cfg = EncoderConfig(kind="id_level", features=10, dim=16,
                            levels=4)
        params = encoding.init_id_level(jax.random.key(1), cfg)
        x = jnp.asarray(geom_rng(3, 10, 9).random(
            (3, 10), dtype=np.float32))
        poisoned = dict(params, levels=params["levels"].at[0].set(
            jnp.where(params["levels"][0] > 0, jnp.nan,
                      params["levels"][0])))
        # Keep valid columns away from level 0 so only the padded
        # columns ever gather the poisoned level.
        x_hi = 0.75 + 0.25 * x  # quantizes to levels >= 2
        want = encoding.encode_id_level(params, x_hi, chunk=10)
        got = encoding.encode_id_level(poisoned, x_hi, chunk=128)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestHierarchicalSemantics:
    """Coarse-to-fine corners the differential sweep can't pin: explicit
    tie-breaking on duplicated columns, the planted-cluster recall
    property, and the degenerate S = G bit-exactness contract."""

    def test_shortlist_ties_break_to_lower_id(self):
        rng = geom_rng(40)
        base = bipolar(rng, (4, 128))
        # Duplicate every super-centroid: ids 0..3 == ids 4..7.
        supers = jnp.concatenate([base, base], axis=0)
        q = bipolar(rng, (5, 128))
        qp, spt = ops.pack_rows(q), ops.pack_rows(supers).T
        gi, gs = ops.am_shortlist(qp, spt, n_dims=128, s=8,
                                  use_kernel=True)
        wi, ws = ref.am_shortlist(qp, spt, 128, 8)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
        gi, gs = np.asarray(gi), np.asarray(gs)
        for r in range(gi.shape[0]):
            pos = {int(gi[r, a]): a for a in range(8)}
            for i in range(4):
                # Copy pair (i, i + 4) ties: equal sims, lower id first.
                assert gs[r, pos[i]] == gs[r, pos[i + 4]]
                assert pos[i] < pos[i + 4]
            # Global invariant: equal-sim runs are ordered by id.
            for a in range(7):
                assert (gs[r, a] > gs[r, a + 1]
                        or (gs[r, a] == gs[r, a + 1]
                            and gi[r, a] < gi[r, a + 1]))

    @pytest.mark.parametrize("case", ["null_slots", "few_candidates",
                                      "disjoint_rows"])
    def test_am_search_sparse_layouts(self, case):
        # Layout corners of the kernel's tile reads, each against the
        # oracle: slots that copy nothing, rows left short of k, and a
        # block whose rows read disjoint tiles.
        rng = geom_rng(43, len(case))
        if case == "null_slots":
            # One 3-tile group sets max_tiles; the 1-tile groups pad
            # their slots with the null tile. 12 rows in blocks of 8:
            # the padded rows of the last block read only null slots.
            assign = np.repeat(np.arange(4), [300, 20, 60, 20])
            short = np.stack([rng.permutation(4)[:2] for _ in range(12)])
            layout, table, _ = sparse_parity(rng, 130, assign, 4, short,
                                             ks=(1, 4), block_b=8)
            assert layout.max_tiles == 3
            assert np.mean(table == layout.null_tile) > 0.3
        elif case == "few_candidates":
            # Groups of 2, 3 and 4 centroids: every row's two groups
            # hold at most 7 candidates, fewer than k = 9.
            assign = np.repeat(np.arange(3), [2, 3, 4])
            short = np.stack([rng.permutation(3)[:2] for _ in range(5)])
            _, _, (idx, sims) = sparse_parity(rng, 128, assign, 3, short,
                                              ks=(1, 9))
            n_cand = np.bincount(assign)[short].sum(axis=1)
            for r in range(5):
                assert np.all(idx[r, n_cand[r]:] == -1)
                assert np.all(sims[r, n_cand[r]:]
                              == np.finfo(np.float32).min)
                assert np.all(idx[r, :n_cand[r]] >= 0)
        else:
            # 16 one-tile groups; row r shortlists groups 2r and 2r + 1,
            # so no two rows of the one 8-row block share a tile.
            assign = np.repeat(np.arange(16), 100)
            short = np.arange(16).reshape(8, 2)
            layout, table, _ = sparse_parity(rng, 128, assign, 16, short,
                                             ks=(1, 3), block_b=8)
            rows = [set(t) - {layout.null_tile} for t in table.tolist()]
            assert all(len(r) == 2 for r in rows)
            assert len(set().union(*rows)) == 16

    def test_sparse_ties_break_on_original_id(self):
        # Two clusters each holding one copy of every (duplicated)
        # centroid; with both clusters shortlisted, the winner per tie
        # pair must be the lower ORIGINAL id even though the layout
        # permutation scattered the copies into different tiles.
        from repro.deploy import hierarchical as hier
        rng = geom_rng(41)
        base = bipolar(rng, (6, 128))
        am = jnp.concatenate([base, base], axis=0)        # ids 0..5 == 6..11
        assign = np.array([0, 1] * 6, np.int32)           # interleaved
        apt = np.asarray(ops.pack_rows(am).T)
        layout = hier.build_layout(apt, assign, 2)
        q = bipolar(rng, (4, 128))
        qp = ops.pack_rows(q)
        short = jnp.broadcast_to(jnp.arange(2, dtype=jnp.int32)[None],
                                 (4, 2))
        idx, sims = ops.am_search_sparse(
            qp, jnp.asarray(layout.slab), jnp.asarray(layout.col_ids),
            short, jnp.asarray(layout.tile_start),
            jnp.asarray(layout.tile_count), n_dims=128, k=12,
            max_tiles=layout.max_tiles, use_kernel=True)
        idx, sims = np.asarray(idx), np.asarray(sims)
        for r in range(4):
            pos = {int(idx[r, a]): a for a in range(12)}
            for i in range(6):
                assert sims[r, pos[i]] == sims[r, pos[i + 6]]
                assert pos[i] < pos[i + 6]
            for a in range(11):
                assert (sims[r, a] > sims[r, a + 1]
                        or (sims[r, a] == sims[r, a + 1]
                            and idx[r, a] < idx[r, a + 1]))

    def _planted(self, rng, c, g, d=128, flip=0.05):
        protos = rng.choice(np.array([-1.0, 1.0], np.float32),
                            size=(g, d))
        assign = rng.integers(0, g, size=c)
        am = protos[assign]
        am = np.where(rng.random(am.shape) < flip, -am, am)
        return am.astype(np.float32), assign

    def test_recall_at_paper_scale(self):
        # Planted clusters at C=1024, G=32: the full pipeline (kmeans
        # clustering + coarse shortlist + sparse fine search) must find
        # the true best centroid for >= 99% of noisy queries at S=8.
        import jax as _jax
        from repro.deploy import hierarchical as hier
        rng = np.random.default_rng(99)
        c, g, d, s = 1024, 32, 128, 8
        am, _ = self._planted(rng, c, g, d)
        src = rng.integers(0, c, size=256)
        q = am[src]
        q = np.where(rng.random(q.shape) < 0.08, -q, q)
        spt, layout = hier.build_search_state(
            _jax.random.PRNGKey(0), am, g, kmeans_iters=6,
            kmeans_sample=1024)
        qp = ops.pack_rows(jnp.asarray(q))
        short, _ = ops.am_shortlist(qp, spt, n_dims=d, s=s)
        idx, sims = ops.am_search_sparse(
            qp, jnp.asarray(layout.slab), jnp.asarray(layout.col_ids),
            short, jnp.asarray(layout.tile_start),
            jnp.asarray(layout.tile_count), n_dims=d, k=1,
            max_tiles=layout.max_tiles)
        exact = (q.astype(np.float32) @ am.T).max(axis=1)
        recall = float(np.mean(np.asarray(sims)[:, 0] == exact))
        assert recall >= 0.99, f"recall@1 {recall} < 0.99 at S={s}"

    def test_s_equals_g_is_bit_exact_with_flat_scan(self):
        import jax as _jax
        from repro.deploy import hierarchical as hier
        rng = np.random.default_rng(7)
        c, g, d = 300, 16, 130  # ragged C and D
        am, _ = self._planted(rng, c, g, d)
        spt, layout = hier.build_search_state(
            _jax.random.PRNGKey(1), am, g, kmeans_iters=4,
            kmeans_sample=300)
        q = rng.choice(np.array([-1.0, 1.0], np.float32), size=(9, d))
        qp = ops.pack_rows(jnp.asarray(q))
        apt = ops.pack_rows(jnp.asarray(am)).T
        short, _ = ops.am_shortlist(qp, spt, n_dims=d, s=g)
        idx, sims = ops.am_search_sparse(
            qp, jnp.asarray(layout.slab), jnp.asarray(layout.col_ids),
            short, jnp.asarray(layout.tile_start),
            jnp.asarray(layout.tile_count), n_dims=d, k=1,
            max_tiles=layout.max_tiles)
        fi, fs = ops.am_search_packed(qp, apt, n_dims=d)
        np.testing.assert_array_equal(np.asarray(idx)[:, 0],
                                      np.asarray(fi))
        np.testing.assert_array_equal(np.asarray(sims)[:, 0],
                                      np.asarray(fs))


# -- hypothesis-generated packed-path inputs --------------------------------
# Guarded (not importorskip) so a missing hypothesis skips ONLY the
# property class — the deterministic differential sweep above must run
# everywhere.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - dev extra, see requirements-dev
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    SETTINGS = dict(max_examples=20, deadline=None)

    @st.composite
    def packed_geometry(draw):
        """Random (B, D, C, seed); D lands on any byte boundary."""
        b = draw(st.integers(1, 8))
        d = draw(st.integers(1, 96))
        c = draw(st.integers(1, 40))
        seed = draw(st.integers(0, 2**31 - 1))
        return b, d, c, seed

    class TestPackedPathProperties:
        @settings(**SETTINGS)
        @given(packed_geometry())
        def test_pack_roundtrip(self, geom):
            b, d, _, seed = geom
            rng = np.random.default_rng(seed)
            x = jnp.asarray(rng.choice([-1.0, 1.0], size=(b, d))
                            .astype(np.float32))
            p = ops.pack_rows(x)
            np.testing.assert_array_equal(np.asarray(p),
                                          np.asarray(ref.pack_rows(x)))
            u = np.asarray(ops.unpack_bits(p))
            np.testing.assert_array_equal(u[:, :d], np.asarray(x))
            assert np.all(u[:, d:] == -1.0)  # tail bits packed as 0

        @settings(**SETTINGS)
        @given(packed_geometry(), st.sampled_from(["popcount", "unpack"]))
        def test_packed_search_parity(self, geom, mode):
            b, d, c, seed = geom
            rng = np.random.default_rng(seed)
            q = jnp.asarray(rng.choice([-1.0, 1.0], size=(b, d))
                            .astype(np.float32))
            am = jnp.asarray(rng.choice([-1.0, 1.0], size=(c, d))
                             .astype(np.float32))
            qp = ops.pack_rows(q)
            apt = ops.pack_rows(am).T
            gi, gs = ops.am_search_packed(qp, apt, n_dims=d, mode=mode)
            wi, ws = ref.am_search(q, am.T)
            np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
            np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))

        @settings(**SETTINGS)
        @given(packed_geometry())
        def test_encode_pack_parity(self, geom):
            b, d, c, seed = geom
            f = max(1, c)  # reuse the C draw as a ragged feature count
            rng = np.random.default_rng(seed)
            x = jnp.asarray(rng.random((b, f), dtype=np.float32))
            w = jnp.asarray(rng.choice([-1.0, 1.0], size=(f, d))
                            .astype(np.float32))
            np.testing.assert_array_equal(
                np.asarray(ops.encode_pack(x, w)),
                np.asarray(ref.encode_pack(x, w)))

    @st.composite
    def layout_geometry(draw):
        """Random (C, G, seed) for cluster-layout invariants."""
        c = draw(st.integers(1, 200))
        g = draw(st.integers(1, 24))
        seed = draw(st.integers(0, 2**31 - 1))
        return c, g, seed

    class TestClusterLayoutProperties:
        """build_layout invariants: the physical permutation is a
        bijection and every centroid lands in exactly one tile range —
        the contract the sparse gather's correctness rests on."""

        @settings(**SETTINGS)
        @given(layout_geometry())
        def test_layout_invariants(self, geom):
            from repro.deploy import hierarchical as hier
            c, g, seed = geom
            rng = np.random.default_rng(seed)
            am = rng.choice([-1.0, 1.0], size=(c, 64)).astype(np.float32)
            apt = np.asarray(ops.pack_rows(jnp.asarray(am)).T)
            assign = rng.integers(0, g, size=c).astype(np.int32)
            layout = hier.build_layout(apt, assign, g)
            col_ids = np.asarray(layout.col_ids)
            starts = np.asarray(layout.tile_start)
            counts = np.asarray(layout.tile_count)

            # Permutation bijection: the valid slab columns hold every
            # original centroid id exactly once, and nothing else.
            valid = col_ids[col_ids >= 0]
            assert sorted(valid.tolist()) == list(range(c))
            # Each centroid sits in exactly one cluster's tile range,
            # and it is its OWN cluster's range.
            sizes = np.bincount(assign, minlength=g)
            for grp in range(g):
                lo, hi = starts[grp] * 128, (starts[grp]
                                             + counts[grp]) * 128
                ids_here = col_ids[lo:hi]
                ids_here = ids_here[ids_here >= 0]
                assert len(ids_here) == sizes[grp]
                assert np.all(assign[ids_here] == grp)
                # ceil-division tile accounting, never over-allocated.
                assert counts[grp] == -(-int(sizes[grp]) // 128) or (
                    sizes[grp] == 0 and counts[grp] in (0, 1))
            # Trailing null tile: all-invalid, shared gather target.
            assert layout.slab.shape[1] == layout.n_tiles * 128
            assert np.all(col_ids[layout.null_tile * 128:] == -1)
            # Slab columns carry the permuted packed payloads.
            for col in range(min(c, 16)):  # spot-check the payload map
                dest = np.nonzero(col_ids == col)[0][0]
                np.testing.assert_array_equal(layout.slab[:, dest],
                                              apt[:, col])

        @settings(**SETTINGS)
        @given(layout_geometry())
        def test_expand_tiles_cover_exactly_the_shortlist(self, geom):
            from repro.deploy import hierarchical as hier
            from repro.kernels.am_search_sparse import (
                expand_shortlist_tiles,
            )
            c, g, seed = geom
            rng = np.random.default_rng(seed)
            am = rng.choice([-1.0, 1.0], size=(c, 64)).astype(np.float32)
            apt = np.asarray(ops.pack_rows(jnp.asarray(am)).T)
            assign = rng.integers(0, g, size=c).astype(np.int32)
            layout = hier.build_layout(apt, assign, g)
            s = min(3, g)
            short = np.stack([rng.permutation(g)[:s] for _ in range(4)])
            tiles = np.asarray(expand_shortlist_tiles(
                jnp.asarray(short.astype(np.int32)),
                jnp.asarray(layout.tile_start),
                jnp.asarray(layout.tile_count),
                max_tiles=layout.max_tiles, null_tile=layout.null_tile))
            col_ids = np.asarray(layout.col_ids)
            starts = np.asarray(layout.tile_start)
            counts = np.asarray(layout.tile_count)
            for r in range(4):
                want = {t for grp in short[r]
                        for t in range(starts[grp],
                                       starts[grp] + counts[grp])}
                got = set(tiles[r].tolist())
                assert got - {layout.null_tile} == want
                # Every centroid of every shortlisted cluster is
                # reachable through the expanded tiles.
                reach = {i for t in got
                         for i in col_ids[t * 128:(t + 1) * 128]
                         if i >= 0}
                assert reach == {int(i) for i in range(c)
                                 if assign[i] in set(short[r].tolist())}
