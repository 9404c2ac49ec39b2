"""Run one cell of the benchmark once on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` by name; its configuration,
traffic mix, correctness limits and per-layer readers are found by the
names given there (see ``bench/__init__.py``). The run needs a TPU with
as many chips as the cell asks for, and exits 3 with no result without
one. The last line of standard output is the result as one JSON object;
the last lines of standard error give each number compared for
``correct`` beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def fail(msg: str, code: int = 3):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def load_cell(name: str) -> dict:
    """The cell, its configuration, traffic, limits and metrics."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r} in BENCHMARK.json", 2)
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads",
                                                          [name])]
    reported = {m["name"] for m in e2e}
    layers = [m for m in spec["per_layer"]
              if name in m.get("workloads", [name])
              and m["moves"] in reported]
    return dict(cell=cell, cfg=cfg, traffic=traffic, limits=limits,
                end_to_end=e2e, per_layer=layers)


def reader(metric: str):
    """The per-layer metric's reader, ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def chips_or_exit(n: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no devices: {e}")
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devs[0].platform}; the benchmark "
             "measures only on the chip")
    if len(devs) < n:
        fail(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             t_start: float, devs) -> tuple:
    """Set up, measure and check one run of the cell ``c``
    (``load_cell``) on ``devs``; returns (result line, check lines)."""
    from bench import harness, systems, work
    mode = importlib.import_module(f"bench.modes.{c['traffic']['mode']}")
    run = harness.Run(seed=seed, seconds=seconds, trace=trace, cfg=c["cfg"],
                      traffic=c["traffic"], t_start=t_start)
    with harness.timed(run.phases, "build"):
        system = systems.build(c["cfg"], seed)
    res = mode.run(run, system)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": c["cell"]["chips"],
              "memory_peak_bytes": run.memory_peak_bytes}
    metrics = {}
    if trace:
        ctx = dict(run=run, cell=c["cell"], cfg=c["cfg"],
                   end_to_end=res.end_to_end,
                   peak=work.peaks(devs[0].device_kind))
        for m in c["per_layer"]:
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        s = run.trace_summary
        device.update(busy_s=s.busy_s, window_s=s.window_s)
    else:
        # A metric named <quantity>.<qualifier> (``rows_per_s.hier``)
        # reports the mode's <quantity> under a bound of its own.
        values = dict(res.end_to_end, setup_s=run.setup_s)
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"].split(".")[0]],
                                  "unit": m["unit"]}

    checks = {}
    correct = res.failed == 0
    for name, limit in c["limits"]["limits"].items():
        value = res.checks[name]
        checks[name] = {"value": value, "limit": limit}
        correct = correct and math.isfinite(value) and value <= limit
    for m in metrics.values():
        correct = correct and math.isfinite(m["value"])
    line = {"correct": bool(correct), "attempted": res.attempted,
            "failed": res.failed, "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = run.trace_summary.breakdown()
    line["checks"] = checks
    notes = [f"setup {name} {sec!r}" for name, sec in run.phases.items()]
    notes.append("setup jaxmon_listener off (recompiles_steady_state reads 0)")
    notes += [f"window {name} {value!r}" for name, value in
              dict(_window_notes(run), **res.notes).items()]
    notes += [f"check {name} {chk['value']!r} limit {chk['limit']!r}"
              for name, chk in checks.items()]
    return line, notes


def _window_notes(run) -> dict:
    """What the window's host did besides the program's work: traces and
    compiles (there should be none), garbage collections, and the
    longest of each of the program's spans."""
    out = {f"jit_{k}": v for k, v in run.counts.get("jit", {}).items()}
    out.update({f"gc_{k}": v for k, v in run.counts.get("gc", {}).items()})
    longest = {}
    for name, dur in run.spans:
        longest[name] = max(longest.get(name, 0), dur)
    out.update({f"longest_{k}_s": v / 1e9 for k, v in longest.items()})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (BENCH / "limits").is_dir() or not (ROOT / "src").is_dir():
        fail("the checkout lacks the program or the benchmark's files")
    c = load_cell(args.workload)
    devs = chips_or_exit(c["cell"]["chips"])

    import jax
    from repro import compile_cache
    from repro.obs import jaxmon
    compile_cache.enable()
    # Cache every program, however quick to compile, so that only a
    # checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # The program's jax.monitoring listener raises when JAX reports a
    # negative duration (its wall-clock timing of a compile), which ends
    # the process; the served and trained paths do not need it. Without
    # it OnlineEngine's recompiles_steady_state reads 0; the run counts
    # the window's compiles itself (harness._JitEvents).
    jaxmon._installed = True
    line, notes = run_cell(c, args.seed, args.seconds, bool(args.trace),
                           T_START, devs)
    for note in notes:
        print(note, file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
