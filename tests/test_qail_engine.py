"""Device-resident QAIL training engine: scan epochs, fused kernel,
encode-once fit, checkpointed resume, unified evaluator."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EncoderConfig, MemhdConfig, MemhdModel, qail
from repro.core import am as am_lib
from repro.core import encoding, evaluate as eval_lib
from repro.core.memhd import MemhdTrainState
from repro.kernels import ops, ref


def _random_problem(rng, n, d, c, k):
    h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    q = jnp.where(h >= 0, 1.0, -1.0)
    y = jnp.asarray(rng.integers(0, k, size=(n,)).astype(np.int32))
    fp = jnp.asarray(rng.normal(size=(c, d)).astype(np.float32))
    owners = jnp.asarray((np.arange(c) % k).astype(np.int32))
    return h, q, y, am_lib.make_am_state(fp, owners)


class TestScanEpoch:
    def test_bit_exact_vs_sequential_at_bs1(self):
        """batch_size=1 + epoch-end refresh == the paper-exact
        sample-by-sample schedule, bit for bit."""
        rng = np.random.default_rng(0)
        n, d, c, k = 97, 64, 16, 4
        h, q, y, state = _random_problem(rng, n, d, c, k)
        cfg = MemhdConfig(dim=d, columns=c, classes=k, lr=0.03,
                          batch_size=1)
        s_seq = qail.qail_epoch_sequential(state, cfg, h, q, y)
        s_scan, _ = qail.qail_epoch_batched(state, cfg, h, q, y,
                                            refresh_every=n)
        np.testing.assert_array_equal(np.asarray(s_seq["fp"]),
                                      np.asarray(s_scan["fp"]))
        np.testing.assert_array_equal(np.asarray(s_seq["binary"]),
                                      np.asarray(s_scan["binary"]))

    @pytest.mark.parametrize("refresh_every", [1, 2, 4])
    def test_tracks_hostloop(self, refresh_every):
        """Scan engine == pre-refactor host loop (fixed semantics),
        including the ragged final batch and mid-epoch refreshes."""
        rng = np.random.default_rng(1)
        n, d, c, k = 101, 32, 12, 3  # 101 % 32 != 0: ragged tail
        h, q, y, state = _random_problem(rng, n, d, c, k)
        cfg = MemhdConfig(dim=d, columns=c, classes=k, lr=0.05,
                          batch_size=32)
        s_hl, mr_hl = qail.qail_epoch_hostloop(
            state, cfg, h, q, y, refresh_every=refresh_every)
        s_sc, mr_sc = qail.qail_epoch_batched(
            state, cfg, h, q, y, refresh_every=refresh_every)
        np.testing.assert_allclose(np.asarray(s_hl["fp"]),
                                   np.asarray(s_sc["fp"]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(s_hl["binary"]),
                                      np.asarray(s_sc["binary"]))
        assert abs(mr_hl - float(mr_sc)) < 1e-6

    def test_no_double_finalize_when_refresh_divides(self, monkeypatch):
        """n_batches % refresh_every == 0 -> the last in-loop refresh IS
        the epoch finalize; the old trailing (redundant) one is gone."""
        calls = {"n": 0}
        orig = qail.qail_finalize_epoch

        def counting(state, cfg):
            calls["n"] += 1
            return orig(state, cfg)

        monkeypatch.setattr(qail, "qail_finalize_epoch", counting)
        rng = np.random.default_rng(2)
        h, q, y, state = _random_problem(rng, 128, 32, 8, 4)
        cfg = MemhdConfig(dim=32, columns=8, classes=4, batch_size=32)
        qail.qail_epoch_hostloop(state, cfg, h, q, y, refresh_every=2)
        assert calls["n"] == 2  # 4 batches / refresh_every=2; NOT 3

        calls["n"] = 0
        qail.qail_epoch_hostloop(state, cfg, h, q, y, refresh_every=3)
        assert calls["n"] == 2  # one at batch 3 + the trailing finalize

    def test_one_dispatch_per_epoch(self):
        """A multi-epoch fit traces the scan-epoch body exactly once and
        never falls back to per-batch python dispatch — the compiled-
        trainer contract (one jit call, one host sync per epoch)."""
        rng = np.random.default_rng(3)
        # Unique geometry so the jit cache can't already hold this shape.
        n, d, c, k = 210, 48, 12, 4
        h, q, y, state = _random_problem(rng, n, d, c, k)
        cfg = MemhdConfig(dim=d, columns=c, classes=k, batch_size=33)
        hb, qb, yb, mask = qail.prebatch(h, q, y, cfg.batch_size)
        before = qail._scan_trace_count
        for _ in range(5):
            state, n_miss = qail.qail_epoch_scan(state, cfg, hb, qb, yb,
                                                 mask)
        assert qail._scan_trace_count - before == 1  # 5 epochs, 1 trace
        assert isinstance(n_miss, jax.Array)  # sync is the caller's call

    def test_prebatch_mask(self):
        h = jnp.ones((5, 4))
        q = jnp.ones((5, 4))
        y = jnp.arange(5, dtype=jnp.int32)
        hb, qb, yb, mask = qail.prebatch(h, q, y, 3)
        assert hb.shape == (2, 3, 4)
        np.testing.assert_array_equal(np.asarray(mask),
                                      [[1, 1, 1], [1, 1, 0]])
        assert int(yb[1, 2]) == -1  # padded label can't match any class


class TestQailUpdateKernel:
    @pytest.mark.parametrize("b,c,d", [(17, 13, 100), (64, 32, 128),
                                       (256, 130, 257), (5, 3, 8),
                                       (33, 128, 512)])
    def test_parity_vs_ref(self, b, c, d):
        rng = np.random.default_rng(b * 1000 + c)
        k = max(2, c // 3)
        q = jnp.asarray(rng.choice([-1., 1.], size=(b, d))
                        .astype(np.float32))
        upd = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
        am_t = jnp.asarray(rng.choice([-1., 1.], size=(d, c))
                           .astype(np.float32))
        owners = jnp.asarray(rng.integers(0, k, size=(c,))
                             .astype(np.int32))
        labels = jnp.asarray(rng.integers(0, k, size=(b,))
                             .astype(np.int32))
        mask = jnp.asarray((rng.random(b) > 0.2).astype(np.float32))
        d_ref, m_ref = ref.qail_update_delta(q, upd, am_t, owners,
                                             labels, mask, 0.05)
        d_k, m_k = ops.qail_update(q, upd, am_t, owners, labels, mask,
                                   lr=0.05)
        np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_ref))
        assert float(m_k) == float(m_ref)

    def test_delta_matches_scatter_path(self):
        """The one-hot-matmul delta == the scatter-based batch update."""
        rng = np.random.default_rng(7)
        n, d, c, k = 64, 32, 16, 4
        h, q, y, state = _random_problem(rng, n, d, c, k)
        cfg = MemhdConfig(dim=d, columns=c, classes=k, lr=0.02,
                          batch_size=n)
        new_state, _ = qail.qail_batch_update(state, cfg, h, q, y)
        scatter_delta = np.asarray(new_state["fp"]) - np.asarray(
            state["fp"])
        mask = jnp.ones((n,), jnp.float32)
        kern_delta, _ = ops.qail_update(
            q, h, state["binary"].T, state["centroid_class"], y, mask,
            lr=cfg.lr)
        np.testing.assert_allclose(np.asarray(kern_delta), scatter_delta,
                                   rtol=1e-5, atol=1e-5)

    def test_scan_epoch_kernel_path(self):
        rng = np.random.default_rng(8)
        n, d, c, k = 100, 64, 16, 4
        h, q, y, state = _random_problem(rng, n, d, c, k)
        cfg = MemhdConfig(dim=d, columns=c, classes=k, lr=0.03,
                          batch_size=32)
        s_jnp, mr_j = qail.qail_epoch_batched(state, cfg, h, q, y)
        s_ker, mr_k = qail.qail_epoch_batched(state, cfg, h, q, y,
                                              use_kernel=True)
        np.testing.assert_allclose(np.asarray(s_jnp["fp"]),
                                   np.asarray(s_ker["fp"]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(s_jnp["binary"]),
                                      np.asarray(s_ker["binary"]))
        assert abs(float(mr_j) - float(mr_k)) < 1e-6


class TestEncodeOnce:
    def test_fit_encodes_training_set_exactly_once(self, small_hdc_data,
                                                   monkeypatch):
        ds = small_hdc_data
        calls = {"n": 0}
        orig = encoding.encode

        def counting(params, cfg, feats):
            calls["n"] += 1
            return orig(params, cfg, feats)

        monkeypatch.setattr(encoding, "encode", counting)
        enc = EncoderConfig(kind="projection", features=ds.features,
                            dim=128)
        amc = MemhdConfig(dim=128, columns=32, classes=ds.classes,
                          epochs=3, kmeans_iters=5, batch_size=128)
        m = MemhdModel.create(jax.random.key(0), enc, amc)
        m.fit(jax.random.key(1), ds.train_x, ds.train_y)
        assert calls["n"] == 1  # init + every epoch share ONE encode


class TestFitSpans:
    def test_fit_spans_nest(self, small_hdc_data):
        """A 3-epoch fit: one ``fit`` holding one ``fit.encode`` and,
        each epoch, a ``fit.epoch`` dispatch then its ``fit.sync``."""
        from repro import obs
        ds = small_hdc_data
        enc = EncoderConfig(kind="projection", features=ds.features,
                            dim=128)
        amc = MemhdConfig(dim=128, columns=32, classes=ds.classes,
                          epochs=3, kmeans_iters=2, batch_size=128)
        m = MemhdModel.create(jax.random.key(0), enc, amc)
        obs.TRACER.reset()
        m.fit(jax.random.key(1), ds.train_x, ds.train_y)
        evs = [e for e in obs.TRACER.events() if e.name.startswith("fit")]
        [fit] = [e for e in evs if e.name == "fit"]
        kids = [e for e in evs if e is not fit]
        assert all(e.parent_id == fit.span_id for e in kids)
        assert [(e.name, (e.args or {}).get("epoch")) for e in kids] == [
            ("fit.encode", None), ("fit.epoch", 1), ("fit.sync", 1),
            ("fit.epoch", 2), ("fit.sync", 2), ("fit.epoch", 3),
            ("fit.sync", 3)]
        ends = [e.start_ns + e.dur_ns for e in kids]
        assert all(fit.start_ns <= e.start_ns for e in kids)
        assert max(ends) <= fit.start_ns + fit.dur_ns
        assert all(a <= b.start_ns for a, b in zip(ends, kids[1:]))


class TestCheckpointedFit:
    def test_resume_is_bit_exact(self, small_hdc_data, tmp_path):
        from repro.checkpoint import CheckpointConfig, CheckpointManager

        ds = small_hdc_data
        enc = EncoderConfig(kind="projection", features=ds.features,
                            dim=128)
        amc = MemhdConfig(dim=128, columns=32, classes=ds.classes,
                          epochs=6, kmeans_iters=5, batch_size=128)
        m = MemhdModel.create(jax.random.key(0), enc, amc)

        m_clean, _ = m.fit(jax.random.key(1), ds.train_x, ds.train_y)

        ck = CheckpointManager(CheckpointConfig(str(tmp_path / "ck")))
        m.fit(jax.random.key(1), ds.train_x, ds.train_y, epochs=4,
              ckpt=ck, ckpt_every=2)  # "crashes" after epoch 4
        m_res, hist = m.fit(jax.random.key(1), ds.train_x, ds.train_y,
                            epochs=6, ckpt=ck, ckpt_every=2)  # resume
        np.testing.assert_array_equal(np.asarray(m_clean.am_state["fp"]),
                                      np.asarray(m_res.am_state["fp"]))
        np.testing.assert_array_equal(
            np.asarray(m_clean.am_state["binary"]),
            np.asarray(m_res.am_state["binary"]))
        # The restored curve is continuous across the resume.
        assert [r["epoch"] for r in hist["curve"]] == [1, 2, 3, 4, 5, 6]

    def test_train_state_roundtrip(self, tmp_path):
        from repro.checkpoint import CheckpointConfig, CheckpointManager

        state = am_lib.make_am_state(
            jnp.arange(12.0).reshape(4, 3), jnp.arange(4))
        ck = CheckpointManager(CheckpointConfig(str(tmp_path / "ts")))
        ck.save(3, MemhdTrainState.create(state, 3))
        step, tree, _ = ck.restore(MemhdTrainState.create(
            jax.tree.map(jnp.zeros_like, state)))
        assert step == 3
        assert int(tree.epoch) == 3
        np.testing.assert_array_equal(np.asarray(tree.am_state["fp"]),
                                      np.asarray(state["fp"]))


class TestFitSharded:
    def test_matches_plain_fit_on_single_device_mesh(self,
                                                     small_hdc_data):
        ds = small_hdc_data
        enc = EncoderConfig(kind="projection", features=ds.features,
                            dim=128)
        amc = MemhdConfig(dim=128, columns=32, classes=ds.classes,
                          epochs=3, kmeans_iters=5, batch_size=128)
        m = MemhdModel.create(jax.random.key(0), enc, amc)
        m_fit, _ = m.fit(jax.random.key(1), ds.train_x, ds.train_y)
        m_sh, hist = m.fit_sharded(jax.random.key(1), ds.train_x,
                                   ds.train_y)
        # Sharded syncs Eq.-6 deltas in bf16 (wire dtype), so the float
        # trajectories differ slightly; the deployed binary AM must
        # agree almost everywhere and accuracy must match closely.
        agree = (np.asarray(m_sh.am_state["binary"])
                 == np.asarray(m_fit.am_state["binary"])).mean()
        assert agree > 0.95, agree
        acc_f = m_fit.score(ds.test_x, ds.test_y)
        acc_s = m_sh.score(ds.test_x, ds.test_y)
        assert abs(acc_f - acc_s) < 0.05, (acc_f, acc_s)
        assert len(hist["curve"]) == 3


class TestUnifiedEvaluator:
    def test_ragged_tail_accuracy(self):
        labels = jnp.asarray(np.arange(10) % 3, dtype=jnp.int32)
        inputs = jnp.asarray(np.arange(10, dtype=np.float32))[:, None]
        # predict_fn: correct iff input index is even
        def predict(x):
            i = x[:, 0].astype(jnp.int32)
            return jnp.where(i % 2 == 0, i % 3, (i + 1) % 3)
        acc = eval_lib.batched_accuracy(predict, inputs, labels, batch=4)
        assert acc == 0.5

    def test_padding_never_counts(self):
        labels = jnp.zeros((5,), jnp.int32)
        inputs = jnp.zeros((5, 2))
        acc = eval_lib.batched_accuracy(
            lambda x: jnp.zeros((x.shape[0],), jnp.int32),
            inputs, labels, batch=4)
        assert acc == 1.0  # 5/5, not 8/5 or 5/8

    def test_qail_evaluate_matches_naive(self):
        rng = np.random.default_rng(11)
        _, q, y, state = _random_problem(rng, 101, 32, 12, 3)
        naive = float(np.mean(np.asarray(
            am_lib.predict(state["binary"], state["centroid_class"], q))
            == np.asarray(y)))
        assert qail.evaluate(state, q, y, batch=32) == pytest.approx(naive)

    def test_deployed_score_uses_padded_evaluator(self, small_hdc_data):
        ds = small_hdc_data
        enc = EncoderConfig(kind="projection", features=ds.features,
                            dim=128)
        amc = MemhdConfig(dim=128, columns=32, classes=ds.classes,
                          epochs=1, kmeans_iters=4, batch_size=128)
        m = MemhdModel.create(jax.random.key(0), enc, amc)
        m, _ = m.fit(jax.random.key(1), ds.train_x, ds.train_y)
        dep = m.deploy(packed=True)
        # 150*10 train samples scored with a non-dividing batch: the
        # ragged tail goes through the padded path and must not change
        # the result vs the model-side evaluator.
        acc_m = m.score(ds.test_x, ds.test_y, batch=96)
        acc_d = dep.score(ds.test_x, ds.test_y, batch=96)
        assert acc_m == pytest.approx(acc_d)
