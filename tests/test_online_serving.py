"""Online serving engine: deadline-aware batching policy, streaming
QAIL folds (drift recovery + live class append on packed AND
hierarchical backends under ShardedArtifact), atomic generation swaps
(pre-swap futures bit-exact on the old artifact), generation
metrics/events, and the zero-steady-state-recompile contract across
shape-stable swaps."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro import obs
from repro.deploy import ShardedArtifact
from repro.serve import (
    Arrival, Feedback, OnlineEngine, OnlineRequest, ServiceModel,
    StreamingUpdater, apply_drift, batch_buckets, feedback_burst,
    merge_events, plan_batch, poisson_arrivals,
)


@pytest.fixture(scope="module")
def ds():
    from repro.data import load_dataset
    return load_dataset("mnist", train_per_class=80, test_per_class=30)


@pytest.fixture(scope="module")
def full_model(ds):
    """Trained on every class — the drift-recovery scenarios."""
    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    enc = EncoderConfig(kind="projection", features=ds.features, dim=256)
    amc = MemhdConfig(dim=256, columns=3 * ds.classes, classes=ds.classes,
                      epochs=3, kmeans_iters=3)
    m = MemhdModel.create(jax.random.key(0), enc, amc)
    m, _ = m.fit(jax.random.key(1), ds.train_x, ds.train_y)
    return m


@pytest.fixture(scope="module")
def partial_model(ds):
    """Trained WITHOUT the last class — the live-append scenarios."""
    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    known = ds.classes - 1
    mask = np.asarray(ds.train_y) < known
    enc = EncoderConfig(kind="projection", features=ds.features, dim=256)
    amc = MemhdConfig(dim=256, columns=3 * known, classes=known,
                      epochs=3, kmeans_iters=3)
    m = MemhdModel.create(jax.random.key(0), enc, amc)
    m, _ = m.fit(jax.random.key(1), np.asarray(ds.train_x)[mask],
                 np.asarray(ds.train_y)[mask])
    return m, known


def _est(_rows):
    return 0.003


def _req(rid, rows=4, t=0.0, deadline_ms=None, f=6):
    return OnlineRequest(rid=rid, feats=np.zeros((rows, f), np.float32),
                         t_arrival=t, deadline_ms=deadline_ms)


class TestPlanBatch:
    """The admission policy, as pure unit checks."""

    def test_empty_queue_waits(self):
        assert plan_batch([], 0.0, max_batch=16,
                          estimate_rows_s=_est) == 0

    def test_full_batch_closes(self):
        q = [_req(i, rows=8) for i in range(3)]
        assert plan_batch(q, 0.0, max_batch=16,
                          estimate_rows_s=_est) == 2

    def test_underfull_best_effort_waits(self):
        q = [_req(0, rows=4, t=0.0)]
        assert plan_batch(q, 0.001, max_batch=16, estimate_rows_s=_est,
                          max_wait_s=0.05) == 0

    def test_max_wait_closes(self):
        q = [_req(0, rows=4, t=0.0)]
        assert plan_batch(q, 0.06, max_batch=16, estimate_rows_s=_est,
                          max_wait_s=0.05) == 1

    def test_tight_deadline_closes(self):
        # Deadline 10ms, service estimate 3ms, margin 2ms: at t=6ms the
        # slack (10 - 6 - 3 = 1ms) is under the margin -> close now.
        q = [_req(0, rows=4, t=0.0, deadline_ms=10.0)]
        assert plan_batch(q, 0.006, max_batch=16, estimate_rows_s=_est,
                          margin_s=0.002, max_wait_s=1.0) == 1

    def test_loose_deadline_waits(self):
        q = [_req(0, rows=4, t=0.0, deadline_ms=500.0)]
        assert plan_batch(q, 0.006, max_batch=16, estimate_rows_s=_est,
                          margin_s=0.002, max_wait_s=1.0) == 0

    def test_inflight_eta_tightens_slack(self):
        # Same instant as the loose case, but 490ms of queued-up
        # in-flight work ahead of us eats the entire budget.
        q = [_req(0, rows=4, t=0.0, deadline_ms=500.0)]
        assert plan_batch(q, 0.006, max_batch=16, estimate_rows_s=_est,
                          inflight_eta_s=0.49, margin_s=0.002,
                          max_wait_s=1.0) == 1

    def test_flush_closes_any_nonempty(self):
        q = [_req(0, rows=1)]
        assert plan_batch(q, 0.0, max_batch=16, estimate_rows_s=_est,
                          flush=True) == 1

    def test_never_splits_requests(self):
        # 10 + 10 rows into max_batch 16: only the head request closes.
        q = [_req(0, rows=10), _req(1, rows=10)]
        assert plan_batch(q, 0.0, max_batch=16, estimate_rows_s=_est,
                          flush=True) == 1


class TestBucketsAndServiceModel:
    def test_geometric_grid(self):
        assert batch_buckets(8, 64) == [8, 16, 32, 64]
        assert batch_buckets(8, 60) == [8, 16, 32, 64]
        assert batch_buckets(8, 8) == [8]

    def test_bad_args_raise(self):
        with pytest.raises(ValueError):
            batch_buckets(0, 64)

    def test_ewma_and_nearest_bucket_fallback(self):
        sm = ServiceModel(default_s=0.01, alpha=0.5)
        assert sm.estimate(16) == 0.01  # blind default
        sm.observe(16, 0.004)
        assert sm.estimate(16) == 0.004
        sm.observe(16, 0.008)
        assert sm.estimate(16) == pytest.approx(0.006)
        # An unseen larger bucket scales from the nearest known one.
        assert sm.estimate(32) == pytest.approx(0.012)


class TestStreamHelpers:
    def test_merge_orders_feedback_before_arrivals(self):
        a = Arrival(t=1.0, request=_req(0))
        f = Feedback(t=1.0, feats=np.zeros((1, 6), np.float32),
                     labels=np.zeros(1, np.int64))
        assert merge_events([a], [f]) == [f, a]

    def test_poisson_class_filter(self, ds):
        te_y = np.asarray(ds.test_y)
        evs = poisson_arrivals(np.asarray(ds.test_x), n_requests=20,
                               rate_qps=100, labels_pool=te_y,
                               classes=[0, 1], seed=3)
        assert len(evs) == 20
        for ev in evs:
            assert set(np.unique(ev.request.labels)) <= {0, 1}
        # times strictly increase and deadlines default to None
        ts = [ev.t for ev in evs]
        assert ts == sorted(ts)
        assert evs[0].request.t_deadline is None

    def test_feedback_burst_chunks_fold_on_last(self):
        x = np.zeros((10, 6), np.float32)
        y = np.arange(10)
        evs = feedback_burst(x, y, t=2.0, chunk=4, fold=True)
        assert [e.feats.shape[0] for e in evs] == [4, 4, 2]
        assert [e.fold for e in evs] == [False, False, True]

    def test_apply_drift_bounds(self):
        x = np.random.default_rng(0).normal(size=(4, 9)).astype(np.float32)
        np.testing.assert_allclose(apply_drift(x, 0.0), x)
        assert apply_drift(x, 0.5).dtype == np.float32
        with pytest.raises(ValueError):
            apply_drift(x, 1.5)


class TestStreamingUpdater:
    def test_fold_empty_buffer_returns_none(self, full_model):
        upd = StreamingUpdater(full_model,
                               full_model.deploy(target="packed"))
        assert upd.fold() is None
        assert upd.generation == 0

    def test_buffer_cap_drops_oldest(self, full_model):
        upd = StreamingUpdater(full_model,
                               full_model.deploy(target="packed"),
                               buffer_cap=10)
        x = np.zeros((6, 4), np.float32)
        upd.ingest(x, np.zeros(6))
        upd.ingest(x + 1, np.ones(6))
        assert upd.buffered == 6  # first chunk evicted whole
        upd.ingest(np.zeros((25, 4), np.float32), np.zeros(25))
        assert upd.buffered == 10  # single oversized chunk truncated

    def test_should_fold_policy(self, full_model):
        upd = StreamingUpdater(full_model,
                               full_model.deploy(target="packed"),
                               fold_every=8)
        upd.ingest(np.zeros((5, 4), np.float32), np.zeros(5))
        assert not upd.should_fold
        upd.ingest(np.zeros((5, 4), np.float32), np.zeros(5))
        assert upd.should_fold

    def test_drifted_fold_recovers_accuracy(self, ds, full_model):
        """The headline streaming claim: labeled drifted feedback folded
        through QAIL recovers accuracy on the drifted distribution, and
        the same-geometry swap is shape-stable."""
        dep = full_model.deploy(target="packed")
        tx, ty = np.asarray(ds.test_x), np.asarray(ds.test_y)
        dx = apply_drift(tx, 0.5)
        acc_before = np.mean(np.asarray(dep.predict(dx)) == ty)
        upd = StreamingUpdater(full_model, dep, fold_epochs=3)
        upd.ingest(apply_drift(np.asarray(ds.train_x), 0.5),
                   np.asarray(ds.train_y))
        result = upd.fold()
        acc_after = np.mean(np.asarray(upd.artifact.predict(dx)) == ty)
        assert result.shape_stable
        assert result.n_new_classes == 0
        assert result.generation == 1 and upd.generation == 1
        assert 0.0 <= result.miss_rate <= 1.0
        assert acc_after >= acc_before + 0.05, (acc_before, acc_after)
        # Shape-stable swap: serving the new artifact at an
        # already-warm batch shape compiles nothing.
        warm = dx[:32]
        jax.block_until_ready(upd.artifact.predict(warm))
        upd.ingest(apply_drift(np.asarray(ds.train_x), 0.5),
                   np.asarray(ds.train_y))
        assert upd.fold().shape_stable
        with obs.assert_no_recompiles("post-swap warm-shape predict"):
            jax.block_until_ready(upd.artifact.predict(warm))


class TestGenerationObservability:
    def test_gauge_histogram_and_event_log(self, ds, full_model,
                                           tmp_path):
        path = tmp_path / "events.jsonl"
        upd = StreamingUpdater(full_model,
                               full_model.deploy(target="packed"),
                               events=obs.EventLog(str(path)))
        before = obs.REGISTRY.get("update_fold_ms")
        n_before = sum(v["count"] for _, v in before.series()) \
            if before is not None else 0
        upd.ingest(np.asarray(ds.train_x)[:32],
                   np.asarray(ds.train_y)[:32])
        result = upd.fold()
        assert obs.gauge("model_generation").value() == 1.0
        hist = obs.REGISTRY.get("update_fold_ms")
        assert sum(v["count"] for _, v in hist.series()) == n_before + 1
        lines = [json.loads(line) for line
                 in path.read_text().splitlines()]
        folds = [rec for rec in lines if rec["event"] == "model_fold"]
        assert len(folds) == 1
        assert folds[0]["generation"] == 1
        assert folds[0]["n_samples"] == 32
        assert folds[0]["shape_stable"] is True
        assert folds[0]["fold_ms"] == pytest.approx(result.fold_ms,
                                                    abs=0.01)


class TestClassAppend:
    """Acceptance: a class never seen at training time is appended
    mid-serving — on the packed AND hierarchical backends, under the
    multi-device ShardedArtifact wrapper — and the swap is atomic."""

    @pytest.mark.parametrize("target", ["packed", "hierarchical"])
    def test_append_new_class_sharded(self, ds, partial_model, target):
        model, known = partial_model
        dep = ShardedArtifact(model.deploy(target=target), devices=1)
        upd = StreamingUpdater(model, dep, fold_epochs=3)
        tr_x, tr_y = np.asarray(ds.train_x), np.asarray(ds.train_y)
        te_x, te_y = np.asarray(ds.test_x), np.asarray(ds.test_y)
        new_test = te_x[te_y == known]
        # Before: the held-out class cannot be predicted (label space
        # ends at known-1).
        assert np.asarray(dep.predict(new_test)).max() < known
        new = tr_y == known
        upd.ingest(tr_x[new], tr_y[new])
        result = upd.fold()
        assert result.n_new_classes == 1
        assert not result.shape_stable  # (D,C) grew -> re-deploy
        assert upd.model.am_cfg.classes == known + 1
        assert isinstance(upd.artifact, ShardedArtifact)
        # jit caches survive the swap: the wrapper shares its _fns table
        assert upd.artifact._fns is dep._fns
        preds = np.asarray(upd.artifact.predict(new_test))
        frac_new = np.mean(preds == known)
        assert frac_new >= 0.5, frac_new
        # Old classes keep working (no catastrophic forgetting from one
        # append fold).
        old_test = te_x[te_y < known]
        acc_old = np.mean(np.asarray(upd.artifact.predict(old_test))
                          == te_y[te_y < known])
        assert acc_old >= 0.3, acc_old

    def test_preswap_inflight_bit_exact(self, ds, partial_model):
        """A future dispatched against generation N must resolve to
        generation-N results even when the swap to N+1 lands before the
        host looks at it — the artifact is an immutable jit operand."""
        model, known = partial_model
        dep = ShardedArtifact(model.deploy(target="packed"), devices=1)
        upd = StreamingUpdater(model, dep, fold_epochs=1)
        te_x = np.asarray(ds.test_x)[:48]
        want_old = np.asarray(dep.predict(te_x))  # warm + reference
        old_artifact = upd.artifact
        fut = old_artifact.predict(te_x)  # in flight across the swap
        tr_y = np.asarray(ds.train_y)
        new = tr_y == known
        upd.ingest(np.asarray(ds.train_x)[new], tr_y[new])
        upd.fold()
        assert upd.artifact is not old_artifact  # replaced, not mutated
        np.testing.assert_array_equal(np.asarray(fut), want_old)
        # And the old generation still answers identically post-swap.
        np.testing.assert_array_equal(
            np.asarray(old_artifact.predict(te_x)), want_old)


class TestOnlineEngine:
    def _engine(self, model, target="packed", **kw):
        dep = model.deploy(target=target)
        upd = StreamingUpdater(model, dep, fold_epochs=1)
        kw.setdefault("max_batch", 32)
        kw.setdefault("max_wait_ms", 5.0)
        return OnlineEngine(upd, **kw)

    def test_empty_stream(self, full_model):
        eng = self._engine(full_model)
        report = eng.serve([])
        assert report["requests"] == 0
        assert report["pad_overhead"] is None
        assert report["lat_ms_p50"] is None
        assert report["recompiles_steady_state"] == 0

    def test_oversized_request_rejected(self, full_model):
        eng = self._engine(full_model, max_batch=16)
        big = OnlineRequest(rid=0,
                            feats=np.zeros((17, 64), np.float32))
        with pytest.raises(ValueError, match="max_batch"):
            eng.serve([Arrival(t=0.0, request=big)])

    def test_stream_serves_every_request_bit_exact(self, ds,
                                                   full_model):
        eng = self._engine(full_model, depth=2)
        evs = poisson_arrivals(np.asarray(ds.test_x), n_requests=30,
                               rate_qps=3000, max_size=6,
                               labels_pool=np.asarray(ds.test_y),
                               seed=7)
        report = eng.serve(evs)
        assert report["requests"] == 30
        assert report["recompiles_steady_state"] == 0
        assert report["rows"] == sum(e.request.size for e in evs)
        assert report["rows_padded"] % eng.tile == 0
        dep = eng.artifact
        for ev in evs:
            np.testing.assert_array_equal(
                eng.responses[ev.request.rid],
                np.asarray(dep.predict(ev.request.feats)))

    def test_dispatch_spans_carry_queue_wait(self, ds, full_model):
        """The ``dispatch`` spans' ``wait_ms_sum`` over ``requests`` is
        the mean arrival -> dispatch wait of the served requests, and
        every span of a batch carries the batch's sequence number."""
        eng = self._engine(full_model, depth=2)
        evs = poisson_arrivals(np.asarray(ds.test_x), n_requests=30,
                               rate_qps=3000, max_size=6,
                               deadline_ms=10.0, seed=12)
        waits = []
        drain = eng._drain_one

        def spy():
            f = eng._inflight[0]
            waits.extend(f.t_dispatch - r.t_arrival for r in f.requests)
            drain()

        eng._drain_one = spy
        obs.TRACER.reset()
        eng.serve(evs)
        spans = obs.TRACER.events()
        disp = [e.args for e in spans if e.name == "dispatch"]
        assert sum(a["requests"] for a in disp) == len(waits) == 30
        assert (sum(a["wait_ms_sum"] for a in disp) / 30
                == pytest.approx(np.mean(waits) * 1e3))
        assert (max(a["wait_ms_max"] for a in disp)
                == pytest.approx(max(waits) * 1e3))
        batch_spans = {}
        for e in spans:
            batch_spans.setdefault(e.args["batch"], []).append(e.name)
        assert len(batch_spans) == len(disp) > 1
        for names in batch_spans.values():
            assert sorted(names) == ["device_wait", "dispatch",
                                     "host_prep", "pad"]

    def test_shape_stable_swap_zero_recompiles(self, ds, full_model):
        """Tentpole contract: a mid-stream drift fold swaps the model
        with ZERO steady-state recompiles — every compile in the run
        sits inside the warmup/fold windows and the rewarm window is
        never entered."""
        eng = self._engine(full_model, depth=2)
        tx, ty = np.asarray(ds.test_x), np.asarray(ds.test_y)
        ev1 = poisson_arrivals(tx, n_requests=20, rate_qps=3000,
                               max_size=6, labels_pool=ty, seed=8)
        t = ev1[-1].t + 1e-3
        fb = feedback_burst(apply_drift(np.asarray(ds.train_x), 0.4),
                            np.asarray(ds.train_y), t=t, fold=True)
        ev2 = poisson_arrivals(apply_drift(tx, 0.4), n_requests=20,
                               rate_qps=3000, max_size=6,
                               labels_pool=ty, start=t, rid_base=1000,
                               seed=9)
        report = eng.serve(merge_events(ev1, fb, ev2))
        assert report["requests"] == 40
        assert report["model_generation"] == 1
        gen = report["generations"][0]
        assert gen["shape_stable"] is True
        assert gen["steady_recompiles_before_swap"] == 0
        assert report["recompiles_steady_state"] == 0
        assert report["recompiles_excluded"]["rewarm"] == 0
        json.dumps(report)  # report stays a JSON document

    def test_mid_stream_class_append(self, ds, partial_model):
        """Acceptance: the engine appends a never-seen class live and
        post-swap requests predict it; the growth recompiles land in
        the excluded fold/rewarm windows, steady state stays at zero."""
        model, known = partial_model
        eng = self._engine(model, depth=2)
        tx, ty = np.asarray(ds.test_x), np.asarray(ds.test_y)
        ev1 = poisson_arrivals(tx, n_requests=16, rate_qps=3000,
                               max_size=6, labels_pool=ty,
                               classes=range(known), seed=10)
        t = ev1[-1].t + 1e-3
        tr_y = np.asarray(ds.train_y)
        new = tr_y == known
        fb = feedback_burst(np.asarray(ds.train_x)[new], tr_y[new],
                            t=t, fold=True)
        ev2 = poisson_arrivals(tx, n_requests=16, rate_qps=3000,
                               max_size=6, labels_pool=ty,
                               classes=[known], start=t, rid_base=1000,
                               seed=11)
        report = eng.serve(merge_events(ev1, fb, ev2))
        assert report["model_generation"] == 1
        gen = report["generations"][0]
        assert gen["shape_stable"] is False
        assert gen["n_new_classes"] == 1
        assert gen["classes"] == known + 1
        assert report["recompiles_steady_state"] == 0
        assert report["recompiles_excluded"]["rewarm"] > 0
        hits = total = 0
        for ev in ev2:
            pred = np.asarray(eng.responses[ev.request.rid])
            hits += int((pred == known).sum())
            total += pred.shape[0]
        assert hits / total >= 0.5, (hits, total)
