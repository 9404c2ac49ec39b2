"""The obs layer: metrics registry semantics (bucket boundaries,
snapshot schema, Prometheus exposition), span nesting + Chrome-trace
export, JAX runtime introspection (recompile counting under a
deliberately shape-ragged jit), the dispatch-tier counters for all
nine kernels, unified logging, and the JSONL event stream."""
import json
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import jaxmon
from repro.obs.logs import EventLog, setup_logging
from repro.obs.metrics import Registry, log_buckets
from repro.obs.trace import Tracer


# ---------------------------------------------------------------- metrics

class TestLogBuckets:
    def test_log_spacing_and_coverage(self):
        bs = log_buckets(0.1, 100.0, per_decade=1)
        assert bs[0] == pytest.approx(0.1)
        assert bs[-1] >= 100.0
        ratios = [b / a for a, b in zip(bs, bs[1:])]
        assert all(r == pytest.approx(10.0, rel=1e-6) for r in ratios)

    def test_per_decade_density(self):
        bs = log_buckets(1.0, 10.0, per_decade=4)
        # 4 steps per decade: 1, 10^.25, 10^.5, 10^.75, 10
        assert len(bs) == 5
        assert bs[2] == pytest.approx(10 ** 0.5, rel=1e-9)

    def test_bad_args_rejected(self):
        with pytest.raises(ValueError):
            log_buckets(0.0, 10.0)
        with pytest.raises(ValueError):
            log_buckets(10.0, 1.0)
        with pytest.raises(ValueError):
            log_buckets(1.0, 10.0, per_decade=0)


class TestHistogram:
    def test_bucket_boundaries_inclusive_upper(self):
        reg = Registry()
        h = reg.histogram("h", buckets=[1.0, 10.0, 100.0])
        for v in (0.5, 1.0, 1.01, 10.0, 99.0, 100.0, 1e6):
            h.observe(v)
        snap = reg.snapshot()["h"]
        assert snap["buckets"] == [1.0, 10.0, 100.0]
        # Cumulative: <=1: {0.5, 1.0}; <=10: +{1.01, 10.0};
        # <=100: +{99.0, 100.0}; +Inf: +{1e6}.
        assert snap["values"][""]["counts"] == [2, 4, 6, 7]
        assert snap["values"][""]["count"] == 7
        assert snap["values"][""]["sum"] == pytest.approx(
            0.5 + 1.0 + 1.01 + 10.0 + 99.0 + 100.0 + 1e6)

    def test_labeled_series_are_independent(self):
        reg = Registry()
        h = reg.histogram("h", buckets=[1.0])
        h.observe(0.5, stage="a")
        h.observe(2.0, stage="b")
        snap = reg.snapshot()["h"]["values"]
        assert snap['stage="a"']["counts"] == [1, 1]
        assert snap['stage="b"']["counts"] == [0, 1]

    def test_unsorted_buckets_rejected(self):
        with pytest.raises(ValueError, match="increasing"):
            Registry().histogram("h", buckets=[10.0, 1.0])


class TestRegistry:
    def test_snapshot_schema_stable(self):
        """The snapshot dict is the --metrics-out contract: exact key
        set per instrument type, canonical sorted-label series keys."""
        reg = Registry()
        reg.counter("c", "help c").inc(2, b="2", a="1")
        reg.gauge("g").set(5.0)
        reg.histogram("h", buckets=[1.0]).observe(0.5)
        snap = reg.snapshot()
        assert list(snap) == ["c", "g", "h"]  # sorted names
        assert set(snap["c"]) == {"type", "help", "values"}
        assert set(snap["g"]) == {"type", "help", "values"}
        assert set(snap["h"]) == {"type", "help", "buckets", "values"}
        assert snap["c"]["type"] == "counter"
        # Label order in the call does not leak into the series key.
        assert list(snap["c"]["values"]) == ['a="1",b="2"']
        assert snap["c"]["values"]['a="1",b="2"'] == 2.0
        assert set(snap["h"]["values"][""]) == {"counts", "sum", "count"}
        # Identical state -> identical snapshot, and JSON-serializable.
        assert snap == reg.snapshot()
        json.dumps(snap)

    def test_idempotent_registration_and_kind_conflict(self):
        reg = Registry()
        c1 = reg.counter("x")
        assert reg.counter("x") is c1
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("h", buckets=[1.0])
            reg.histogram("h", buckets=[2.0])

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            Registry().counter("c").inc(-1)

    def test_reset_keeps_families_live(self):
        """Listeners hold instrument references across reset()."""
        reg = Registry()
        c = reg.counter("c")
        c.inc(5)
        reg.reset()
        assert c.value() == 0.0
        c.inc()  # the old handle still feeds the registry
        assert reg.snapshot()["c"]["values"][""] == 1.0

    def test_thread_safety_of_counter(self):
        reg = Registry()
        c = reg.counter("c")

        def work():
            for _ in range(2000):
                c.inc(thread="x")

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value(thread="x") == 8000.0

    def test_prometheus_exposition(self):
        reg = Registry()
        reg.counter("reqs", "requests").inc(3, code="200")
        reg.histogram("lat", buckets=[1.0, 10.0]).observe(0.5)
        text = reg.render_prometheus()
        assert "# HELP reqs requests" in text
        assert "# TYPE reqs counter" in text
        assert 'reqs{code="200"} 3' in text
        assert 'lat_bucket{le="1"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_sum 0.5" in text
        assert "lat_count 1" in text


# ------------------------------------------------------------------ trace

class TestTrace:
    def test_span_nesting_parent_ids(self):
        tr = Tracer()
        with tr.span("outer"):
            with tr.span("mid"):
                with tr.span("inner"):
                    pass
            with tr.span("mid2"):
                pass
        evs = {e.name: e for e in tr.events()}
        assert evs["inner"].parent_id == evs["mid"].span_id
        assert evs["mid"].parent_id == evs["outer"].span_id
        assert evs["mid2"].parent_id == evs["outer"].span_id
        assert evs["outer"].parent_id == 0
        # Nesting also shows in the timestamps: children are contained.
        assert evs["inner"].start_ns >= evs["mid"].start_ns
        assert (evs["inner"].start_ns + evs["inner"].dur_ns
                <= evs["mid"].start_ns + evs["mid"].dur_ns)

    def test_chrome_trace_json_valid(self, tmp_path):
        tr = Tracer()
        with tr.span("a", answer=42, note="x"):
            with tr.span("b"):
                pass
        path = tr.export(str(tmp_path / "t.json"))
        with open(path) as f:
            trace = json.load(f)
        assert set(trace) >= {"traceEvents", "displayTimeUnit"}
        evs = trace["traceEvents"]
        assert len(evs) == 2
        for e in evs:
            assert set(e) == {"name", "ph", "ts", "dur", "pid", "tid",
                              "args"}
            assert e["ph"] == "X"
            assert e["dur"] >= 0
        a = next(e for e in evs if e["name"] == "a")
        assert a["args"]["answer"] == 42 and a["args"]["note"] == "x"

    def test_span_survives_exception(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert [e.name for e in tr.events()] == ["boom"]
        assert tr.current_span_id() == 0  # stack unwound

    def test_bounded_recorder_drops_not_grows(self):
        tr = Tracer(max_events=2)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.events()) == 2
        assert tr.dropped == 3
        assert tr.to_chrome_trace()["otherData"]["dropped_events"] == 3

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer()
        tr.enabled = False
        with tr.span("x"):
            pass
        assert tr.events() == []

    def test_device_bridge_is_noop_safe(self, monkeypatch):
        """Every span enters a profiler annotation of its plain name
        (arguments stay out of it); a disabled tracer enters none and
        hands out one shared no-op context."""
        from repro.obs import trace
        entered = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                entered.append("/" + self.name)

        monkeypatch.setattr(trace, "_ANNOTATION", Annotation)
        tr = Tracer()
        with tr.span("annotated", batch=3):
            with tr.span("inner"):
                jnp.ones((4,)).block_until_ready()
        assert entered == ["annotated", "inner", "/inner", "/annotated"]
        assert [e.name for e in tr.events()] == ["inner", "annotated"]
        assert tr.events()[1].args == {"batch": 3}
        tr.enabled = False
        entered.clear()
        off = tr.span("annotated", batch=4)
        assert off is tr.span("other")
        with off:
            pass
        assert entered == [] and len(tr.events()) == 2

    def test_span_on_profiler_clock(self, tmp_path):
        """Under an active profile the span is a host event of the
        trace, on the Python thread's line, under its plain name."""
        from jax.profiler import ProfileData
        tr = Tracer()
        jnp.ones((4,)).block_until_ready()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                with tr.span("dispatch", batch=7):
                    jnp.ones((8,)).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        [path] = list(tmp_path.rglob("*.xplane.pb"))
        lines = [[e.name for e in line.events] for p in
                 ProfileData.from_file(str(path)).planes
                 if p.name == "/host:CPU" for line in p.lines]
        line = next(ln for ln in lines if "bench.window" in ln)
        assert "dispatch" in line
        assert not any(n.startswith("dispatch#") for ln in lines
                       for n in ln)
        [ev] = tr.events()
        assert ev.args == {"batch": 7}


# ----------------------------------------------------------------- jaxmon

class TestJaxmon:
    def test_recompile_counter_under_shape_ragged_jit(self):
        """A deliberately ragged call sequence: every new shape is a
        fresh trace + compile; repeats are cache hits and count 0."""
        obs.install()

        @jax.jit
        def f(x):
            return (x * 2.0).sum()

        shapes = [(4,), (8,), (12,)]
        for shape in shapes:  # warm one compile per shape
            f(jnp.ones(shape)).block_until_ready()
        n0 = jaxmon.compiles()
        for shape in shapes:  # all cached: no compile events
            f(jnp.ones(shape)).block_until_ready()
        assert jaxmon.compiles() == n0
        with obs.count_compiles() as delta:
            f(jnp.ones((16,))).block_until_ready()  # ragged: recompiles
            assert delta() >= 1

    def test_assert_no_recompiles_raises_and_passes(self):
        obs.install()

        @jax.jit
        def g(x):
            return x + 1.0

        g(jnp.ones((6,))).block_until_ready()
        with obs.assert_no_recompiles("steady"):
            g(jnp.ones((6,))).block_until_ready()
        with pytest.raises(obs.RecompileError, match="steady"):
            with obs.assert_no_recompiles("steady"):
                g(jnp.ones((7,))).block_until_ready()

    def test_install_idempotent(self):
        obs.install()
        before = jaxmon.compiles()
        timed = obs.snapshot()["jax_compile_seconds"]["values"]
        timed = timed[""]["count"] if timed else 0
        obs.install()  # second install must not double-register
        jax.jit(lambda x: x - 3.0)(jnp.ones((5,))).block_until_ready()
        delta = jaxmon.compiles() - before
        assert delta >= 1
        # One listener: the compile histogram counts as the counter does.
        snap = obs.snapshot()["jax_compile_seconds"]["values"][""]
        assert snap["count"] - timed == delta

    @pytest.mark.parametrize("dur", [-0.25, float("nan"), float("inf")])
    def test_bad_compile_duration_never_raises(self, dur):
        """JAX times compiles on the wall clock: a negative (or
        non-finite) duration is counted as a compile, left out of the
        compile-time histogram, and never raises."""
        obs.install()
        before = jaxmon.compiles()
        timed = obs.snapshot()["jax_compile_seconds"]["values"]
        timed = timed[""]["count"] if timed else 0
        jaxmon._on_duration(jaxmon.COMPILE_EVENT, dur)
        jaxmon._on_duration("/jax/core/compile/jaxpr_trace_duration", dur)
        assert jaxmon.compiles() == before + 1
        snap = obs.snapshot()["jax_compile_seconds"]["values"]
        assert (snap[""]["count"] if snap else 0) == timed

    def test_memory_gauges_handle_absent_stats(self):
        # CPU devices report no allocator stats: no gauges, no crash.
        out = obs.update_memory_gauges()
        for dev_stats in out.values():
            assert all(isinstance(v, float) for v in dev_stats.values())


# -------------------------------------------------- ops dispatch counting

def _bipolar(rng, shape):
    return jnp.asarray(rng.choice([-1.0, 1.0], size=shape)
                       .astype(np.float32))


class TestDispatchTiers:
    """Every kernel dispatch lands in kernel_dispatch_total with the
    tier that actually served it — the silent-fallback detector."""

    def _counts(self):
        from repro.kernels import ops
        return ops.dispatch_breakdown()

    def _delta(self, before, after, kernel):
        b, a = before.get(kernel, {}), after.get(kernel, {})
        return {t: a.get(t, 0) - b.get(t, 0) for t in a}

    def test_all_nine_kernels_counted(self):
        """binary_mvm, encode_pack, am_search, am_search_imc,
        am_search_multibit, am_search_packed, am_shortlist,
        am_search_sparse, qail_update: one dispatch each, on the tier
        the backend serves them with."""
        from repro.core.types import ImcArrayConfig, ImcSimConfig
        from repro.deploy import hierarchical as hier
        from repro.kernels import ops, ref
        rng = np.random.default_rng(42)
        b, f, d, c = 2, 16, 128, 6
        feats = jnp.asarray(rng.random((b, f), dtype=np.float32))
        proj = _bipolar(rng, (f, d))
        q, am = _bipolar(rng, (b, d)), _bipolar(rng, (c, d))
        qp = ops.pack_rows(q)
        apt = ops.pack_rows(am).T
        codes = rng.integers(-1, 2, size=(c, d))
        planes = ref.pack_planes(jnp.asarray(codes + 1), 2)

        before = self._counts()
        ops.encode_mvm(feats, proj)
        ops.encode_pack(feats, proj)
        ops.am_search(q, am)
        ops.am_search_imc(q, am, sim=ImcSimConfig(
            arr=ImcArrayConfig(rows=128, cols=128)))
        ops.am_search_multibit(q, planes)
        ops.am_search_packed(qp, apt, n_dims=d)
        ops.am_shortlist(qp, apt, n_dims=d, s=2)
        g = 2
        assign = rng.integers(0, g, size=c).astype(np.int32)
        layout = hier.build_layout(np.asarray(apt), assign, g)
        short = jnp.zeros((b, 1), jnp.int32)
        ops.am_search_sparse(
            qp, jnp.asarray(layout.slab), jnp.asarray(layout.col_ids),
            short, jnp.asarray(layout.tile_start),
            jnp.asarray(layout.tile_count), n_dims=d, k=1,
            max_tiles=layout.max_tiles)
        owners = jnp.arange(c, dtype=jnp.int32) % 3
        labels = jnp.zeros((b,), jnp.int32)
        mask = jnp.ones((b,), jnp.float32)
        ops.qail_update(q, q, am.T, owners, labels, mask, lr=0.5)
        after = self._counts()

        on_tpu = jax.default_backend() == "tpu"
        auto_tier = "pallas" if on_tpu else "xla-oracle"
        expect = {
            "binary_mvm": "pallas", "encode_pack": "pallas",
            "am_search": "pallas", "am_search_imc": "pallas",
            "am_search_multibit": "pallas",
            "am_search_packed": "pallas",
            "am_shortlist": auto_tier, "am_search_sparse": auto_tier,
            "qail_update": "pallas",
        }
        for kernel, tier in expect.items():
            delta = self._delta(before, after, kernel)
            assert delta.get(tier, 0) >= 1, (kernel, tier, delta)

    def test_ref_tier_counted_separately(self):
        from repro.kernels import ops
        rng = np.random.default_rng(7)
        q, am = _bipolar(rng, (2, 64)), _bipolar(rng, (3, 64))
        before = self._counts()
        ops.am_search(q, am, use_kernel=False)
        ops.am_search(q, am, use_kernel=True)
        delta = self._delta(before, self._counts(), "am_search")
        assert delta.get("ref", 0) == 1
        assert delta.get("pallas", 0) == 1

    def test_geometry_label_present(self):
        from repro.kernels import ops
        rng = np.random.default_rng(8)
        q, am = _bipolar(rng, (4, 32)), _bipolar(rng, (5, 32))
        ops.am_search(q, am)
        fam = obs.REGISTRY.get("kernel_dispatch_total")
        geoms = [labels["geometry"] for labels, _ in fam.series()
                 if labels.get("kernel") == "am_search"]
        assert "B=4,C=5,D=32" in geoms

    def test_sparse_dispatch_names_its_path(self):
        """The sparse fine pass labels which path served it: the
        kernel reading tiles from the slab by DMA, or the oracle's XLA
        gather."""
        from repro.deploy import hierarchical as hier
        from repro.kernels import ops
        rng = np.random.default_rng(9)
        am = _bipolar(rng, (6, 128))
        qp = ops.pack_rows(_bipolar(rng, (2, 128)))
        layout = hier.build_layout(np.asarray(ops.pack_rows(am).T),
                                   np.array([0, 1] * 3, np.int32), 2)
        fam = obs.REGISTRY.get("kernel_dispatch_total")

        def served(path):
            return sum(v for labels, v in fam.series()
                       if labels.get("kernel") == "am_search_sparse"
                       and labels["geometry"].endswith(f"path={path}"))

        for use_kernel, path in ((True, "slab-dma"),
                                 (False, "xla-gather")):
            before = served(path)
            ops.am_search_sparse(
                qp, jnp.asarray(layout.slab), jnp.asarray(layout.col_ids),
                jnp.zeros((2, 1), jnp.int32),
                jnp.asarray(layout.tile_start),
                jnp.asarray(layout.tile_count), n_dims=128, k=1,
                max_tiles=layout.max_tiles, use_kernel=use_kernel)
            assert served(path) == before + 1


# ------------------------------------------------------------------- logs

class TestLogging:
    def test_human_format(self, capsys):
        setup_logging()
        logging.getLogger("fmt_test").info("hello %d", 7)
        err = capsys.readouterr().err
        assert "I fmt_test :: hello 7" in err

    def test_json_mode_emits_parseable_lines(self, capsys):
        setup_logging(json_mode=True)
        logging.getLogger("json_test").warning("careful")
        err = capsys.readouterr().err.strip().splitlines()
        rec = json.loads(err[-1])
        assert rec["level"] == "WARNING"
        assert rec["logger"] == "json_test"
        assert rec["msg"] == "careful"
        assert isinstance(rec["ts"], float)
        setup_logging()  # restore the human default for later tests

    def test_event_log_jsonl(self, tmp_path):
        path = tmp_path / "run" / "events.jsonl"
        with EventLog(str(path)) as ev:
            ev.emit("epoch", step=1, miss=0.25)
            ev.emit("checkpoint", step=1, dur_s=0.01)
        lines = path.read_text().strip().splitlines()
        assert len(lines) == 2
        recs = [json.loads(ln) for ln in lines]
        assert recs[0]["event"] == "epoch" and recs[0]["step"] == 1
        assert recs[1]["event"] == "checkpoint"
        assert all("ts" in r for r in recs)

    def test_event_log_none_path_is_noop(self):
        ev = EventLog(None)
        ev.emit("anything", x=1)  # must not raise or write
        ev.close()
