"""Perf-trajectory harness: frozen BENCH_*.json schema, regression-gate
behavior on synthetic baselines, run.py --only/--fast selection semantics
(subprocess), benchmarks/*.py registration completeness, and the
kernel-autotune cache round-trip + tuned-vs-default bit-exactness for
all five tunable kernels."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import gate, record
from repro.kernels import autotune

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_SRC = os.path.join(REPO_ROOT, "src")


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    """Every test starts and ends without a process-global recorder."""
    record.finish(write=False)
    yield
    record.finish(write=False)


class TestRecorderSchema:
    """BENCH_<name>.json is a parsing contract; its key set is frozen."""

    def test_frozen_top_level_schema(self, tmp_path):
        record.start("demo", out_dir=str(tmp_path))
        from benchmarks.common import row
        row("demo/metric_a", 12.5, "acc=0.9")
        row("demo/metric_b", 0.0, 42, cycles=7)
        path = record.finish()
        assert path == str(tmp_path / "BENCH_demo.json")
        with open(path) as f:
            data = json.load(f)
        assert set(data) == set(record.TOP_LEVEL_KEYS)
        assert data["schema_version"] == record.SCHEMA_VERSION == 1
        assert data["bench"] == "demo"
        assert isinstance(data["created_unix"], int)
        for metric in data["metrics"].values():
            assert record.METRIC_REQUIRED_KEYS <= set(metric)
        assert data["metrics"]["demo/metric_a"]["us_per_call"] == 12.5
        assert data["metrics"]["demo/metric_a"]["derived"] == "acc=0.9"
        assert data["metrics"]["demo/metric_b"]["cycles"] == 7

    def test_timing_stats_true_median_and_min(self):
        # The old sorted[n // 2] was the UPPER-middle sample for even n.
        stats = record.timing_stats([4e-6, 1e-6, 2e-6, 3e-6])
        assert stats["p50_us"] == pytest.approx(2.5)  # not 3.0
        assert stats["min_us"] == pytest.approx(1.0)
        assert stats["n_samples"] == 4
        assert stats["p95_us"] == pytest.approx(4.0)
        assert stats["p99_us"] == pytest.approx(4.0)
        odd = record.timing_stats([3e-6, 1e-6, 2e-6])
        assert odd["p50_us"] == pytest.approx(2.0)

    def test_time_fn_attaches_stats_to_row(self, tmp_path):
        from benchmarks.common import row, time_fn
        record.start("timed", out_dir=str(tmp_path))
        us = time_fn(lambda: np.arange(8), iters=4)
        row("timed/thing", us, "x")
        path = record.finish()
        with open(path) as f:
            metric = json.load(f)["metrics"]["timed/thing"]
        assert record.TIMING_KEYS <= set(metric)
        assert metric["p50_us"] == metric["us_per_call"] == us
        assert metric["min_us"] <= metric["p50_us"] <= metric["p95_us"]
        assert metric["n_samples"] == 4
        assert len(metric["samples_us"]) == 4

    def test_row_and_time_fn_without_recorder_are_noops(self):
        from benchmarks.common import row, time_fn
        assert record.active() is None
        us = time_fn(lambda: 1, iters=2)
        assert row("orphan", us, "ok").startswith("orphan,")

    def test_from_report_wraps_serving_reports(self, tmp_path):
        report = {"workload": "memhd_classify", "backend": "packed",
                  "qps": 123.4, "lat_ms_p50": 2.0, "bit_exact": True,
                  "devices": 2}
        path = record.from_report("serve_memhd", report,
                                  out_dir=str(tmp_path))
        with open(path) as f:
            data = json.load(f)
        assert set(data) == set(record.TOP_LEVEL_KEYS)
        assert data["bench"] == "serve_memhd"
        # Strings/bools -> meta; numbers -> metrics; lat_ms_* -> timed.
        assert data["meta"]["workload"] == "memhd_classify"
        assert data["meta"]["bit_exact"] is True
        assert data["metrics"]["qps"]["value"] == 123.4
        assert data["metrics"]["qps"]["us_per_call"] == 0.0
        assert data["metrics"]["lat_ms_p50"]["us_per_call"] == 2000.0


def _write_record(dirpath, bench, metrics):
    os.makedirs(dirpath, exist_ok=True)
    rec = {"schema_version": record.SCHEMA_VERSION, "bench": bench,
           "created_unix": 0, "git_sha": None, "jax_backend": "cpu",
           "jax_version": "0", "meta": {}, "metrics": metrics}
    with open(os.path.join(dirpath, f"BENCH_{bench}.json"), "w") as f:
        json.dump(rec, f)


def _timed(us):
    return {"us_per_call": us, "derived": "x", "min_us": us}


class TestGate:
    """gate.py semantics on synthetic baseline/current trees."""

    def _dirs(self, tmp_path):
        return str(tmp_path / "base"), str(tmp_path / "cur")

    def test_identical_passes(self, tmp_path):
        base, cur = self._dirs(tmp_path)
        for d in (base, cur):
            _write_record(d, "k", {"m": _timed(1000.0)})
        assert gate.main(["--baseline", base, "--current", cur]) == 0

    def test_slowdown_fails(self, tmp_path, capsys):
        base, cur = self._dirs(tmp_path)
        _write_record(base, "k", {"m": _timed(1000.0)})
        _write_record(cur, "k", {"m": _timed(3000.0)})  # 200% > 100%
        assert gate.main(["--baseline", base, "--current", cur]) == 1
        assert "slower" in capsys.readouterr().err

    def test_threshold_is_respected(self, tmp_path):
        base, cur = self._dirs(tmp_path)
        _write_record(base, "k", {"m": _timed(1000.0)})
        _write_record(cur, "k", {"m": _timed(1300.0)})  # +30%
        args = ["--baseline", base, "--current", cur]
        assert gate.main(args) == 0  # default 100%
        assert gate.main(args + ["--max-slowdown-pct", "10"]) == 1

    def test_speedup_passes(self, tmp_path):
        base, cur = self._dirs(tmp_path)
        _write_record(base, "k", {"m": _timed(9000.0)})
        _write_record(cur, "k", {"m": _timed(1000.0)})
        assert gate.main(["--baseline", base, "--current", cur]) == 0

    def test_missing_metric_fails(self, tmp_path, capsys):
        base, cur = self._dirs(tmp_path)
        _write_record(base, "k", {"m": _timed(1000.0),
                                  "gone": {"us_per_call": 0.0,
                                           "derived": "1"}})
        _write_record(cur, "k", {"m": _timed(1000.0)})
        assert gate.main(["--baseline", base, "--current", cur]) == 1
        assert "missing" in capsys.readouterr().err

    def test_missing_bench_fails(self, tmp_path):
        base, cur = self._dirs(tmp_path)
        _write_record(base, "k", {"m": _timed(1.0)})
        _write_record(base, "gone", {"m": _timed(1.0)})
        _write_record(cur, "k", {"m": _timed(1.0)})
        assert gate.main(["--baseline", base, "--current", cur]) == 1

    def test_new_bench_and_metric_pass(self, tmp_path):
        base, cur = self._dirs(tmp_path)
        _write_record(base, "k", {"m": _timed(1000.0)})
        _write_record(cur, "k", {"m": _timed(1000.0),
                                 "extra": _timed(5.0)})
        _write_record(cur, "brand_new", {"m": _timed(1.0)})
        assert gate.main(["--baseline", base, "--current", cur]) == 0

    def test_lost_timing_fails(self, tmp_path, capsys):
        base, cur = self._dirs(tmp_path)
        _write_record(base, "k", {"m": _timed(1000.0)})
        _write_record(cur, "k", {"m": {"us_per_call": 0.0,
                                       "derived": "x"}})
        assert gate.main(["--baseline", base, "--current", cur]) == 1
        assert "no timing" in capsys.readouterr().err

    def test_noise_floor_ignores_tiny_timings(self, tmp_path):
        base, cur = self._dirs(tmp_path)
        _write_record(base, "k", {"m": _timed(3.0)})
        _write_record(cur, "k", {"m": _timed(30.0)})  # 10x, but < 50us
        assert gate.main(["--baseline", base, "--current", cur]) == 0

    def test_empty_sides_fail_loudly(self, tmp_path, capsys):
        base, cur = self._dirs(tmp_path)
        os.makedirs(base), os.makedirs(cur)
        assert gate.main(["--baseline", base, "--current", cur]) == 1
        _write_record(base, "k", {"m": _timed(1.0)})
        assert gate.main(["--baseline", base, "--current", cur]) == 1
        assert "no current records" in capsys.readouterr().err

    def test_update_baselines_roundtrip(self, tmp_path):
        base, cur = self._dirs(tmp_path)
        _write_record(cur, "k", {"m": _timed(77.0)})
        assert gate.main(["--baseline", base, "--current", cur,
                          "--update-baselines"]) == 0
        assert gate.main(["--baseline", base, "--current", cur]) == 0

    def test_schema_version_mismatch_fails(self, tmp_path):
        base, cur = self._dirs(tmp_path)
        _write_record(base, "k", {"m": _timed(1000.0)})
        _write_record(cur, "k", {"m": _timed(1000.0)})
        fn = os.path.join(cur, "BENCH_k.json")
        with open(fn) as f:
            data = json.load(f)
        data["schema_version"] = 999
        with open(fn, "w") as f:
            json.dump(data, f)
        assert gate.main(["--baseline", base, "--current", cur]) == 1


def _run_benchrun(*args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args], cwd=REPO_ROOT,
        env=env, capture_output=True, text=True, timeout=timeout)


class TestRunSelection:
    """--only/--fast semantics of benchmarks.run, via subprocess.

    The regression this pins: ``--fast --only fig3`` used to intersect
    the two filters, run NOTHING, and still print the all-passed
    banner with exit code 0.
    """

    def test_only_overrides_fast(self):
        r = _run_benchrun("--fast", "--only", "fig3", "--list")
        assert r.returncode == 0, r.stderr
        listed = [ln.split("\t")[0] for ln in r.stdout.splitlines()
                  if "\t" in ln]
        assert listed == ["fig3"]  # fig3 is NOT in FAST; it still runs
        assert "overrides --fast" in r.stdout

    def test_zero_match_exits_nonzero(self):
        for extra in ([], ["--fast"]):
            r = _run_benchrun(*extra, "--only", "nosuchbench")
            assert r.returncode == 2
            assert "matched zero" in r.stderr
            assert "all" not in r.stdout or "passed" not in r.stdout

    def test_ambiguous_prefix_resolution_is_printed(self):
        r = _run_benchrun("--only", "fig", "--list")
        assert r.returncode == 0, r.stderr
        (resolution,) = [ln for ln in r.stdout.splitlines()
                         if ln.startswith("# --only fig ->")]
        for name in ("fig3", "fig4", "fig5", "fig6", "fig7",
                     "fig_robustness"):
            assert name in resolution

    def test_fast_list_is_the_fast_set(self):
        r = _run_benchrun("--fast", "--list")
        assert r.returncode == 0, r.stderr
        listed = {ln.split("\t")[0] for ln in r.stdout.splitlines()
                  if "\t" in ln}
        from benchmarks.run import FAST
        assert listed == FAST

    @pytest.mark.slow
    def test_recorded_run_end_to_end(self, tmp_path):
        out = str(tmp_path / "rec")
        r = _run_benchrun("--only", "table2", "--record-dir", out)
        assert r.returncode == 0, r.stderr
        assert "# table2 done" in r.stdout
        path = os.path.join(out, "BENCH_table2.json")
        assert os.path.exists(path), os.listdir(tmp_path)
        with open(path) as f:
            data = json.load(f)
        assert set(data) == set(record.TOP_LEVEL_KEYS)
        assert data["bench"] == "table2"
        assert any(k.startswith("table2/") for k in data["metrics"])
        # A recorded run gates green against itself.
        assert gate.main(["--baseline", out, "--current", out]) == 0


# Smallest geometries the kernels are contracted for (D one lane tile).
SMALL_DIMS = {
    "am_search_packed": {"D": 128, "C": 32},
    "am_shortlist": {"D": 128, "G": 32, "S": 4},
    "am_search_sparse": {"D": 128, "T": 2, "K": 3},
    "am_search_multibit": {"D": 128, "C": 32, "bits": 2},
    "encode_pack": {"f": 40, "D": 128},
    "qail_update": {"D": 128, "C": 32},
}


class TestBenchRegistration:
    """Every benchmarks/*.py module is registered in run.py BENCHES (or
    is explicitly harness infrastructure) — pins the orphan-bench class
    of bug (hillclimb shipped unreachable from the orchestrator)."""

    # Harness plumbing, not benches: never registered.
    EXEMPT = {"run", "common", "record", "gate", "__init__"}

    def test_every_bench_module_is_registered(self):
        from benchmarks.run import BENCHES
        bench_dir = os.path.join(REPO_ROOT, "benchmarks")
        modules = {os.path.splitext(f)[0] for f in os.listdir(bench_dir)
                   if f.endswith(".py")}
        registered = {mod.rsplit(".", 1)[-1] for _, mod in BENCHES}
        unregistered = modules - registered - self.EXEMPT
        assert not unregistered, (
            f"benchmarks modules not registered in run.py BENCHES and "
            f"not in the EXEMPT harness set: {sorted(unregistered)}")
        # And the registry never points at a module that doesn't exist.
        assert registered <= modules

    def test_registered_names_are_unique(self):
        from benchmarks.run import BENCHES, FAST
        names = [n for n, _ in BENCHES]
        assert len(names) == len(set(names))
        assert FAST <= set(names)


class TestAutotune:
    """Cache round-trip + tuned-vs-default bit-exactness, all kernels."""

    @pytest.fixture(autouse=True)
    def _tmp_cache(self, tmp_path, monkeypatch):
        self.cache = str(tmp_path / "autotune_cache.json")
        monkeypatch.setenv(autotune.CACHE_ENV, self.cache)

    def test_cache_roundtrip(self):
        dims = SMALL_DIMS["am_search_packed"]
        entry = autotune.autotune_kernel("am_search_packed", dims,
                                         batch=64, iters=1)
        assert os.path.exists(self.cache)
        geom = autotune.geometry_key("am_search_packed", **dims)
        loaded = autotune.lookup("am_search_packed", geom)
        assert loaded is not None
        assert loaded["block_b"] == entry["block_b"]
        assert loaded["geometry"] == geom == "D128_C32"
        assert autotune.tuned_block_b("am_search_packed",
                                      **dims) == entry["block_b"]
        # Unknown geometry falls back to the kernel default.
        assert (autotune.tuned_block_b("am_search_packed", D=999, C=7)
                == autotune.KERNELS["am_search_packed"].default_block_b)

    @pytest.mark.parametrize("kernel", sorted(autotune.KERNELS))
    def test_tuned_vs_default_bit_exact(self, kernel):
        spec = autotune.KERNELS[kernel]
        dims = SMALL_DIMS[kernel]
        # batch > smallest candidates: multi-block tilings are exercised.
        args = spec.make_inputs(np.random.default_rng(3), 96, dims)
        want = [np.asarray(x) for x in jax.tree.leaves(spec.run_ref(*args))]
        for bb in set(spec.candidates) | {spec.default_block_b}:
            got = jax.tree.leaves(spec.run(bb, *args))
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), w,
                                              err_msg=f"{kernel}@{bb}")

    def test_entry_beats_or_ties_default_and_is_recorded(self):
        entry = autotune.autotune_kernel(
            "qail_update", SMALL_DIMS["qail_update"], batch=128, iters=1)
        assert entry["best_us"] <= entry["default_us"]
        assert str(min(entry["block_b"], 128)) in entry["candidates_us"]
        assert entry["backend"] == "cpu"

    def test_ops_dispatch_consults_cache(self):
        from repro.kernels import ops, ref
        dims = SMALL_DIMS["am_search_packed"]
        geom = autotune.geometry_key("am_search_packed", **dims)
        autotune.save_entry({
            "kernel": "am_search_packed",
            "backend": jax.default_backend(),
            "geometry": geom, "block_b": 32})
        assert ops.tuned_block_b("am_search_packed", None, **dims) == 32
        assert ops.tuned_block_b("am_search_packed", 64, **dims) == 64
        # And the cached tiling serves bit-exact predictions.
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.choice([-1., 1.], size=(50, 128))
                        .astype(np.float32))
        am = jnp.asarray(rng.choice([-1., 1.], size=(32, 128))
                         .astype(np.float32))
        qp, apt = ref.pack_rows(q), ref.pack_rows(am).T
        gi, gs = ops.am_search_packed(qp, apt, n_dims=128)
        wi, ws = ref.am_search_packed(qp, apt, 128)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))

    def test_vmem_budget_skips_and_can_exhaust(self):
        dims = SMALL_DIMS["am_search_packed"]
        with pytest.raises(RuntimeError, match="VMEM budget"):
            autotune.autotune_kernel("am_search_packed", dims, batch=64,
                                     iters=1, vmem_budget_mb=1e-6)
        entry = autotune.autotune_kernel(
            "am_search_packed", dims, batch=1024, iters=1,
            vmem_budget_mb=0.125)  # 128 KiB: only block_b=64 fits
        assert entry["skipped_vmem"]

    def test_geometry_key_requires_dims(self):
        with pytest.raises(KeyError, match="missing"):
            autotune.geometry_key("encode_pack", D=64)
