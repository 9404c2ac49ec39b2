"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per benchmark and records
every bench into a persistent ``BENCH_<name>.json`` artifact (schema in
``benchmarks/record.py``; default dir ``benchmarks/results/``, override
with ``--record-dir`` / ``$MEMHD_BENCH_DIR``, disable with
``--no-record``). ``benchmarks.gate`` diffs those artifacts against the
committed ``benchmarks/baselines/`` set and fails on regressions.

Selection: ``--only fig3`` (prefix match; comma-separate for several,
e.g. ``--only table2,fig_robustness``) runs a subset and each token's
resolution is printed before anything runs; a token matching zero
benches exits non-zero immediately. An explicit ``--only`` OVERRIDES
``--fast`` — ``--fast`` alone runs the curated fast set (skips the
minutes-long accuracy sweeps). ``--list`` prints the resolved
selection and exits without running.
"""
import argparse
import sys
import time
import traceback
from typing import List, Tuple

from benchmarks import record

BENCHES = [
    ("table2", "benchmarks.table2_imc_mapping"),
    ("fig7", "benchmarks.fig7_energy"),
    ("kernel", "benchmarks.kernel_bench"),
    ("packed", "benchmarks.packed_vs_unpacked"),
    ("pipeline", "benchmarks.pipeline_bench"),
    ("train_throughput", "benchmarks.train_throughput"),
    ("serve_scaling", "benchmarks.serve_scaling"),
    ("online_serving", "benchmarks.online_serving"),
    ("fig_robustness", "benchmarks.fig_robustness"),
    ("fig3", "benchmarks.fig3_accuracy_memory"),
    ("fig4", "benchmarks.fig4_heatmap"),
    ("fig5", "benchmarks.fig5_init"),
    ("fig6", "benchmarks.fig6_r_sweep"),
    ("ablation", "benchmarks.ablations"),
    ("roofline", "benchmarks.roofline_report"),
    ("hillclimb", "benchmarks.hillclimb"),
    ("hierarchical_search", "benchmarks.hierarchical_search"),
    ("multibit_frontier", "benchmarks.multibit_frontier"),
]
FAST = {"table2", "fig7", "kernel", "packed", "pipeline",
        "train_throughput", "fig_robustness", "roofline",
        "hierarchical_search", "online_serving", "multibit_frontier"}


def resolve_selection(only: str | None, fast: bool,
                      ) -> List[Tuple[str, str]]:
    """Resolve --only/--fast into the bench list, loudly.

    An explicit ``--only`` overrides ``--fast`` (the old intersection
    semantics made ``--fast --only fig3`` run NOTHING and still print
    the all-passed banner). Every ``--only`` token's matches are
    printed before running; a token that matches zero benches is a
    hard error (exit 2), as is an empty overall selection.
    """
    names = [n for n, _ in BENCHES]
    if only is not None:
        tokens = [tok for tok in only.split(",") if tok]
        if not tokens:
            print("run: error: --only given but empty; known benches: "
                  + ", ".join(names), file=sys.stderr)
            raise SystemExit(2)
        selected: List[str] = []
        for tok in tokens:
            matches = [n for n in names if n.startswith(tok)]
            print(f"# --only {tok} -> "
                  f"{','.join(matches) if matches else '<nothing>'}",
                  flush=True)
            if not matches:
                print(f"run: error: --only token {tok!r} matched zero "
                      f"benches; known benches: {', '.join(names)}",
                      file=sys.stderr)
                raise SystemExit(2)
            selected += [m for m in matches if m not in selected]
        if fast:
            print("# note: explicit --only overrides --fast "
                  f"(running {','.join(selected)})", flush=True)
        keep = set(selected)
        return [(n, m) for n, m in BENCHES if n in keep]
    if fast:
        return [(n, m) for n, m in BENCHES if n in FAST]
    return list(BENCHES)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench name prefixes; "
                         "overrides --fast")
    ap.add_argument("--fast", action="store_true",
                    help="run the curated fast set (no accuracy sweeps)")
    ap.add_argument("--list", action="store_true",
                    help="print the resolved selection and exit")
    ap.add_argument("--record-dir", default=None,
                    help="where BENCH_<name>.json artifacts go "
                         "(default: benchmarks/results/)")
    ap.add_argument("--no-record", action="store_true",
                    help="skip writing BENCH_*.json artifacts")
    args = ap.parse_args(argv)

    selection = resolve_selection(args.only, args.fast)
    if not selection:  # unreachable belt-and-braces: never run nothing
        print("run: error: selection resolved to zero benches",
              file=sys.stderr)
        raise SystemExit(2)
    if args.list:
        for name, module in selection:
            print(f"{name}\t{module}")
        return

    from repro import compile_cache
    compile_cache.enable()
    print("name,us_per_call,derived")
    failures = []
    written = []
    for name, module in selection:
        t0 = time.time()
        if not args.no_record:
            record.start(name, out_dir=args.record_dir)
        try:
            mod = __import__(module, fromlist=["main"])
            mod.main()
            path = record.finish(write=not args.no_record)
            if path:
                written.append(path)
                print(f"# {name} recorded -> {path}", flush=True)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001 — keep the suite running
            record.finish(write=False)  # discard the partial record
            failures.append(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr, flush=True)
    if failures:
        print(f"# FAILED benches: {failures}", file=sys.stderr)
        sys.exit(1)
    print(f"# all {len(selection)} selected benches passed"
          + (f" ({len(written)} BENCH records)" if written else ""))


if __name__ == "__main__":
    main()
