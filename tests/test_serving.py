"""serve_memhd driver: batcher accounting, fused-vs-staged parity on
ragged request streams, the queue/service latency decomposition, the
obs integration (steady-state recompiles, dispatch tiers, trace
export), and the JSON report schema contract."""
import json

import jax
import numpy as np
import pytest

from repro import obs
from repro.launch.serve_memhd import (Request, build_report, make_batches,
                                      metrics_summary, serve_batches,
                                      synthetic_requests)


@pytest.fixture(scope="module")
def served(small_hdc_data):
    """A small trained model deployed packed (fused-servable)."""
    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    ds = small_hdc_data
    enc = EncoderConfig(kind="projection", features=ds.features, dim=128)
    amc = MemhdConfig(dim=128, columns=32, classes=ds.classes,
                      epochs=1, kmeans_iters=3)
    m = MemhdModel.create(jax.random.key(0), enc, amc)
    m, _ = m.fit(jax.random.key(1), ds.train_x, ds.train_y)
    return ds, m, m.deploy(packed=True)


def _reqs(sizes, f=4):
    return [Request(rid=i, feats=np.zeros((n, f), np.float32))
            for i, n in enumerate(sizes)]


class TestBatcherAccounting:
    """Padding accounting of the greedy batcher, end to end."""

    def test_pad_accounting_exact(self, served):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=7,
                                  max_size=11, seed=5)
        _, stats = serve_batches(dep, reqs, max_batch=16, tile=8)
        sizes = [r.size for r in reqs]
        batches = make_batches(reqs, 16)
        want_padded = sum(-(-sum(r.size for r in b) // 8) * 8
                          for b in batches)
        assert stats["rows_real"] == sum(sizes)
        assert stats["rows_padded"] == want_padded
        assert stats["batches"] == len(batches)
        assert stats["pad_overhead"] == round(
            want_padded / sum(sizes) - 1, 3)
        assert stats["lat_ms_total"] >= 0

    def test_every_batch_tile_aligned(self, served):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=5,
                                  max_size=13, seed=2)
        _, stats = serve_batches(dep, reqs, max_batch=32, tile=8)
        assert stats["rows_padded"] % 8 == 0
        assert stats["rows_padded"] >= stats["rows_real"]

    def test_batcher_never_splits_requests(self):
        batches = make_batches(_reqs([5, 5, 5, 20, 3]), 12)
        flat = [r.rid for b in batches for r in b]
        assert sorted(flat) == [0, 1, 2, 3, 4]  # every request, once
        assert all(sum(r.size for r in b) <= 12
                   for b in batches if len(b) > 1)


class TestFusedServing:
    """--fused serving: single-dispatch pipeline, bit-exact with staged."""

    def test_fused_vs_staged_parity_on_ragged_stream(self, served):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=11,
                                  max_size=9, seed=7)
        staged, s_stats = serve_batches(dep, reqs, max_batch=24)
        fused, f_stats = serve_batches(dep, reqs, max_batch=24,
                                       fused=True)
        assert staged.keys() == fused.keys()
        for rid in staged:
            np.testing.assert_array_equal(staged[rid], fused[rid])
        # Identical batching either way — only the kernel path differs.
        assert s_stats["rows_padded"] == f_stats["rows_padded"]
        assert s_stats["batches"] == f_stats["batches"]

    def test_predict_features_matches_predict(self, served):
        ds, m, dep = served
        got = np.asarray(dep.predict_features(ds.test_x[:40]))
        np.testing.assert_array_equal(got,
                                      np.asarray(dep.predict(
                                          ds.test_x[:40])))
        np.testing.assert_array_equal(got,
                                      np.asarray(m.predict(
                                          ds.test_x[:40])))

    def test_unfusable_artifact_falls_back_to_staged(self, served):
        ds, m, _ = served
        dep_u = m.deploy(packed=False)
        assert not dep_u.fusable
        np.testing.assert_array_equal(
            np.asarray(dep_u.predict_features(ds.test_x[:16])),
            np.asarray(dep_u.predict(ds.test_x[:16])))


class TestDoubleBuffering:
    """The double-buffered batcher: identical responses at any depth."""

    def test_depths_agree(self, served):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=9,
                                  max_size=7, seed=11)
        sync, s_stats = serve_batches(dep, reqs, max_batch=24, depth=1)
        for depth in (2, 4):
            buf, b_stats = serve_batches(dep, reqs, max_batch=24,
                                         depth=depth)
            assert sync.keys() == buf.keys()
            for rid in sync:
                np.testing.assert_array_equal(sync[rid], buf[rid])
            # Batching/padding accounting is independent of the depth;
            # the depth field tags which latency semantics apply.
            assert b_stats["rows_padded"] == s_stats["rows_padded"]
            assert b_stats["batches"] == s_stats["batches"]
            assert b_stats["depth"] == depth and s_stats["depth"] == 1

    def test_bad_depth_rejected(self, served):
        ds, _, dep = served
        with pytest.raises(ValueError, match="depth"):
            serve_batches(dep, _reqs([4]), depth=0)


class TestTopkServing:
    """--topk serving through the hierarchical backend's fused top-k
    epilogue: per-request (n, k) class matrices whose first column is
    the argmax path, bit for bit (defaults are the exact S = G mode)."""

    def test_topk_first_column_matches_argmax(self, served):
        ds, m, dep = served
        dep_h = m.deploy(target="hierarchical")
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=6,
                                  max_size=9, seed=13)
        argmax, _ = serve_batches(dep, reqs, max_batch=16)
        topk, stats = serve_batches(dep_h, reqs, max_batch=16, topk=3)
        assert argmax.keys() == topk.keys()
        for rid in argmax:
            assert topk[rid].shape == (argmax[rid].shape[0], 3)
            np.testing.assert_array_equal(topk[rid][:, 0], argmax[rid])

    def test_topk_ranks_by_similarity(self, served):
        ds, m, _ = served
        dep_h = m.deploy(target="hierarchical")
        x = np.asarray(ds.test_x[:12], np.float32)
        cls, idx, sims = dep_h.predict_topk(x, 4)
        sims = np.asarray(sims)
        assert np.all(sims[:, :-1] >= sims[:, 1:])  # best-first
        assert cls.shape == idx.shape == sims.shape == (12, 4)

    def test_topk_with_fused_rejected(self, served):
        _, _, dep = served
        with pytest.raises(ValueError, match="topk"):
            serve_batches(dep, _reqs([4]), topk=2, fused=True)

    def test_topk_needs_predict_topk(self, served):
        # Backends without a top-k epilogue fail loudly, not silently.
        _, _, dep = served
        assert not hasattr(type(dep), "predict_topk")
        with pytest.raises(AttributeError):
            serve_batches(dep, _reqs([4]), topk=2)


class TestReportSchema:
    """The JSON report is a parsing contract; its key set is frozen.

    ``backend`` + ``devices`` (and the per-device throughput) make
    reports from different deployment backends and device counts
    comparable — asserted here for every registered backend. ``device``
    names what served the stream (platform, kind, count), so a CPU
    report never passes for a chip report.
    """

    KEYS = {
        "workload", "backend", "device", "devices", "packed", "mode",
        "pipeline",
        "topk", "geometry", "requests", "rows", "wall_s", "qps",
        "rows_per_s", "rows_per_s_per_device", "resident_am_bytes",
        "am_memory_ratio", "metrics", "depth", "batches", "rows_real",
        "rows_padded", "pad_overhead",
        "lat_ms_min", "lat_ms_p50", "lat_ms_p95", "lat_ms_p99",
        "lat_ms_total",
        "service_ms_min", "service_ms_p50", "service_ms_p95",
        "service_ms_p99", "service_ms_total",
        "queue_ms_min", "queue_ms_p50", "queue_ms_p95", "queue_ms_p99",
        "queue_ms_total",
    }

    def test_schema_stable(self, served):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=4,
                                  max_size=6, seed=1)
        for fused in (False, True):
            _, stats = serve_batches(dep, reqs, max_batch=16,
                                     fused=fused)
            rep = build_report(dep, reqs, stats, wall_s=0.25,
                               fused=fused)
            assert set(rep) == self.KEYS
            assert rep["pipeline"] == ("fused" if fused else "staged")
            assert rep["topk"] == 0  # argmax serving
            assert rep["workload"] == "memhd_classify"
            assert rep["backend"] == "packed"
            assert rep["devices"] == 1
            dev = jax.devices()[0]
            assert rep["device"] == {"platform": dev.platform,
                                     "device_kind": dev.device_kind,
                                     "count": len(jax.devices())}
            assert rep["rows"] == sum(r.size for r in reqs)
            assert rep["qps"] == round(len(reqs) / 0.25, 1)
            assert rep["rows_per_s_per_device"] == rep["rows_per_s"]

    def test_unpacked_report_mode(self, served):
        ds, m, _ = served
        dep_u = m.deploy(packed=False)
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=2,
                                  max_size=4, seed=0)
        _, stats = serve_batches(dep_u, reqs, max_batch=8)
        rep = build_report(dep_u, reqs, stats, wall_s=0.1)
        assert set(rep) == self.KEYS
        assert rep["mode"] == "float" and rep["packed"] is False
        assert rep["backend"] == "unpacked"

    def test_topk_report_key(self, served):
        ds, m, _ = served
        dep_h = m.deploy(target="hierarchical")
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=2,
                                  max_size=4, seed=3)
        _, stats = serve_batches(dep_h, reqs, max_batch=8, topk=3)
        rep = build_report(dep_h, reqs, stats, wall_s=0.1, topk=3)
        assert set(rep) == self.KEYS
        assert rep["topk"] == 3
        assert rep["backend"] == "hierarchical"

    def test_imc_backend_report(self, served):
        ds, m, _ = served
        dep_i = m.deploy(target="imc")
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=2,
                                  max_size=4, seed=0)
        _, stats = serve_batches(dep_i, reqs, max_batch=8)
        rep = build_report(dep_i, reqs, stats, wall_s=0.1)
        assert set(rep) == self.KEYS
        assert rep["backend"] == "imc"
        assert rep["mode"] == "analog" and rep["packed"] is False
        assert rep["resident_am_bytes"] == dep_i.resident_bytes


class TestEmptyStream:
    """An empty request stream must not fabricate latency rows: every
    latency field is None (JSON null) and ``batches`` is 0."""

    LAT_FIELDS = [f"{p}_{s}" for p in ("lat_ms", "service_ms", "queue_ms")
                  for s in ("min", "p50", "p95", "p99", "total")]

    def test_empty_stream_null_latency(self, served):
        _, _, dep = served
        responses, stats = serve_batches(dep, [])
        assert responses == {}
        assert stats["batches"] == 0
        assert stats["rows_real"] == 0 and stats["rows_padded"] == 0
        # No rows -> no overhead RATIO: 0.0 would claim "measured, and
        # perfectly packed"; null says "nothing to measure".
        assert stats["pad_overhead"] is None
        for field in self.LAT_FIELDS:
            assert stats[field] is None, field

    def test_empty_stream_report_is_json(self, served):
        _, _, dep = served
        _, stats = serve_batches(dep, [])
        rep = build_report(dep, [], stats, wall_s=0.0)
        parsed = json.loads(json.dumps(rep))  # nulls survive the trip
        assert parsed["lat_ms_min"] is None
        assert parsed["batches"] == 0
        assert parsed["qps"] == 0.0


class TestLatencyDecomposition:
    """queue_ms + service_ms == lat_ms: the pipeline queue wait that
    depth > 1 used to fold silently into lat_ms is now its own field."""

    def _serve(self, served, depth, n=14):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=n,
                                  max_size=6, seed=3)
        return serve_batches(dep, reqs, max_batch=8, depth=depth)

    def test_depth1_queue_is_zero(self, served):
        _, stats = self._serve(served, depth=1)
        assert stats["batches"] >= 2
        assert stats["queue_ms_total"] == 0.0
        assert stats["service_ms_total"] == pytest.approx(
            stats["lat_ms_total"], abs=0.01 * stats["batches"] + 0.01)

    @pytest.mark.parametrize("depth", [2, 4])
    def test_sum_consistent_at_depth(self, served, depth):
        _, stats = self._serve(served, depth=depth)
        assert stats["batches"] >= 2
        # Per batch queue + service == lat exactly; the fields round to
        # 3 decimals, so totals agree within the rounding budget.
        tol = 0.002 * stats["batches"] + 0.01
        assert (stats["service_ms_total"] + stats["queue_ms_total"]
                == pytest.approx(stats["lat_ms_total"], abs=tol))
        for s in ("min", "p50", "p95", "p99", "total"):
            assert stats[f"queue_ms_{s}"] >= 0.0
            assert stats[f"service_ms_{s}"] >= 0.0


class TestObsIntegration:
    """The acceptance contract: instrumented serving is bit-exact with
    direct prediction, steady-state serving never recompiles, the
    metrics section carries the dispatch-tier breakdown, and the trace
    export is valid Chrome trace-event JSON."""

    def test_predictions_bit_exact_with_uninstrumented(self, served):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=8,
                                  max_size=7, seed=9)
        responses, _ = serve_batches(dep, reqs, max_batch=16, depth=4)
        for r in reqs:
            want = np.asarray(dep.predict(r.feats))
            np.testing.assert_array_equal(responses[r.rid], want)

    def test_steady_state_recompiles_zero(self, served):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=10,
                                  max_size=6, seed=4)
        # Warmup pass compiles every padded shape the stream hits...
        serve_batches(dep, reqs, max_batch=16, depth=4)
        # ...so the steady-state pass must compile NOTHING new.
        with obs.count_compiles() as steady:
            _, stats = serve_batches(dep, reqs, max_batch=16,
                                     warmup=False, depth=4)
        assert steady() == 0
        rep = build_report(
            dep, reqs, stats, wall_s=0.1,
            metrics=metrics_summary(recompiles_steady_state=steady()))
        assert rep["metrics"]["recompiles_steady_state"] == 0
        with obs.assert_no_recompiles("steady-state serving"):
            serve_batches(dep, reqs, max_batch=16, warmup=False,
                          depth=4)

    def test_non_f32_stream_warmup_matches_dtype(self, served):
        """Warmup must pre-compile the dtype the stream actually
        carries: a float16 stream warmed with float32 zeros would hit
        cold jit signatures on every steady-state batch (the regression
        this pins down — warmup now reads ``requests[0].feats.dtype``).
        """
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=10,
                                  max_size=6, seed=4)
        reqs = [Request(rid=r.rid,
                        feats=r.feats.astype(np.float16))
                for r in reqs]
        serve_batches(dep, reqs, max_batch=16, depth=2)  # warmup pass
        with obs.assert_no_recompiles("non-f32 steady-state serving"):
            responses, _ = serve_batches(dep, reqs, max_batch=16,
                                         warmup=False, depth=2)
        for r in reqs:  # and the f16 stream still predicts correctly
            np.testing.assert_array_equal(
                responses[r.rid], np.asarray(dep.predict(r.feats)))

    def test_metrics_section_has_dispatch_tiers(self, served):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=4,
                                  max_size=5, seed=6)
        _, stats = serve_batches(dep, reqs, max_batch=16)
        rep = build_report(dep, reqs, stats, wall_s=0.1)
        tiers = rep["metrics"]["dispatch_tiers"]
        # The packed backend serves through pack_rows + the packed scan.
        assert "am_search_packed" in tiers
        assert tiers["am_search_packed"].get("pallas", 0) >= 1
        assert rep["metrics"]["compiles_total"] >= 0
        json.dumps(rep)  # the whole report stays JSON-serializable

    def test_trace_export_is_valid_chrome_trace(self, served, tmp_path):
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=5,
                                  max_size=5, seed=8)
        obs.TRACER.reset()
        serve_batches(dep, reqs, max_batch=16, depth=2)
        path = obs.export_chrome_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            trace = json.load(f)
        events = trace["traceEvents"]
        assert events, "serving emitted no spans"
        names = {e["name"] for e in events}
        assert {"host_prep", "pad", "dispatch", "device_wait"} <= names
        for e in events:
            assert e["ph"] == "X"
            assert e["dur"] >= 0 and e["ts"] > 0
            assert isinstance(e["args"]["span_id"], int)
        # pad spans nest under host_prep: parent ids resolve.
        by_id = {e["args"]["span_id"]: e for e in events}
        pads = [e for e in events if e["name"] == "pad"]
        assert pads
        for p in pads:
            parent = by_id[p["args"]["parent_id"]]
            assert parent["name"] == "host_prep"

    def test_batch_spans_share_a_sequence_number(self, served):
        """Every span of a batch carries the batch's sequence number as
        ``batch=``; the numbers run on across calls, and each call ends
        in one ``stats`` span."""
        ds, _, dep = served
        reqs = synthetic_requests(np.asarray(ds.test_x), n_requests=12,
                                  max_size=5, seed=9)
        obs.TRACER.reset()
        _, first = serve_batches(dep, reqs, max_batch=16, depth=2)
        _, second = serve_batches(dep, reqs, max_batch=16, depth=2)
        evs = obs.TRACER.events()
        assert [e.args for e in evs if e.name == "stats"] == [
            {"batches": first["batches"]}, {"batches": second["batches"]}]
        batch_spans = {}
        for e in evs:
            if e.name != "stats":
                batch_spans.setdefault(e.args["batch"], []).append(e)
        assert len(batch_spans) == first["batches"] + second["batches"]
        for evs in batch_spans.values():
            assert sorted(e.name for e in evs) == [
                "device_wait", "dispatch", "host_prep", "pad"]
            by = {e.name: e for e in evs}
            assert by["pad"].parent_id == by["host_prep"].span_id
