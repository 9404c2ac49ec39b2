"""§Perf hillclimb driver: run named variants of the three selected cells.

Each variant is (cell, overrides) run through the same dry-run path as
the baselines; artifacts land in reports/dryrun/ with override tags and
are compared in EXPERIMENTS.md §Perf.

  PYTHONPATH=src python -m benchmarks.hillclimb --variant dsv3_accum4
  PYTHONPATH=src python -m benchmarks.hillclimb --variant memhd_baseline
  PYTHONPATH=src python -m benchmarks.hillclimb --list

Registered in ``benchmarks.run`` as the ``hillclimb`` bench: the
no-args path runs the paper-representative memhd cell at a reduced
geometry in a SUBPROCESS (the 16x16 production mesh needs
``--xla_force_host_platform_device_count`` set before jax initializes,
which is impossible once the parent run has touched jax) and emits the
roofline terms as bench rows.
"""
import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

if __name__ == "__main__":
    # Only effective when this module IS the entry point (flag must be
    # set before jax initializes); the registered-bench path relies on
    # the subprocess re-exec instead.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512")


def _musicgen_padded_heads():
    """Heads 24 -> 32 so attention shards over the 16-way model axis."""
    from repro.configs import get_config
    cfg = get_config("musicgen-medium")
    blocks = []
    for b in cfg.blocks:
        attn = dataclasses.replace(b.attn, n_heads=32, n_kv_heads=32)
        blocks.append(dataclasses.replace(b, attn=attn))
    return {"blocks": tuple(blocks)}


def _mamba_chunk(q: int):
    from repro.configs import get_config
    cfg = get_config("mamba2-130m")
    blocks = []
    for b in cfg.blocks:
        blocks.append(dataclasses.replace(
            b, ssm=dataclasses.replace(b.ssm, chunk=q)))
    return {"blocks": tuple(blocks)}


def _dsv3_capacity(cf: float):
    from repro.configs import get_config
    cfg = get_config("deepseek-v3-671b")
    blocks = []
    for b in cfg.blocks:
        if b.ffn.kind == "moe":
            b = dataclasses.replace(
                b, ffn=dataclasses.replace(b.ffn, capacity_factor=cf))
        blocks.append(b)
    return {"blocks": tuple(blocks)}


VARIANTS = {
    # --- deepseek-v3-671b x train_4k (most collective-bound) -------------
    "dsv3_accum8": ("deepseek-v3-671b", "train_4k",
                    lambda: {"grad_accum": 8}),
    "dsv3_accum4": ("deepseek-v3-671b", "train_4k",
                    lambda: {"grad_accum": 4}),
    "dsv3_accum2": ("deepseek-v3-671b", "train_4k",
                    lambda: {"grad_accum": 2}),
    "dsv3_cf1_accum4": ("deepseek-v3-671b", "train_4k",
                        lambda: dict(_dsv3_capacity(1.0), grad_accum=4)),
    "dsv3_ep256_accum4": (
        "deepseek-v3-671b", "train_4k",
        lambda: {"grad_accum": 4,
                 "rule_overrides": (("experts", ("model", "data")),)}),
    "dsv3_ep256_accum2": (
        "deepseek-v3-671b", "train_4k",
        lambda: {"grad_accum": 2,
                 "rule_overrides": (("experts", ("model", "data")),)}),
    # --- musicgen-medium x train_4k (worst roofline fraction) ------------
    "musicgen_pad32": ("musicgen-medium", "train_4k",
                       lambda: _musicgen_padded_heads()),
    "musicgen_pad32_accum8": (
        "musicgen-medium", "train_4k",
        lambda: dict(_musicgen_padded_heads(), grad_accum=8)),
    "musicgen_pad32_accum4": (
        "musicgen-medium", "train_4k",
        lambda: dict(_musicgen_padded_heads(), grad_accum=4)),
    "musicgen_accum4": ("musicgen-medium", "train_4k",
                        lambda: {"grad_accum": 4}),
    # --- extras beyond the three required threads ---------------------
    "qwen_decode_int8kv": ("qwen1.5-32b", "decode_32k",
                           lambda: {"kv_cache_quant": True}),
    "gemma3_500k_seqpar": ("gemma3-12b", "long_500k",
                           lambda: {"seq_parallel_decode": True}),
    "mamba2_chunk128": ("mamba2-130m", "train_4k",
                        lambda: _mamba_chunk(128)),
    "mamba2_chunk512": ("mamba2-130m", "train_4k",
                        lambda: _mamba_chunk(512)),
    "musicgen_pad32_fsdp": (
        "musicgen-medium", "train_4k",
        lambda: dict(_musicgen_padded_heads(), fsdp=True)),
}


def run_variant(name: str) -> dict:
    arch, shape, make_overrides = VARIANTS[name]
    from repro.launch.dryrun import run_cell
    rep = run_cell(arch, shape, multi_pod=False,
                   overrides=make_overrides())
    return rep


def run_memhd(dim: int = 1024, columns: int = 1024,
              samples: int = 61_440) -> dict:
    """The paper-representative cell: distributed QAIL epoch."""
    import jax
    from repro.core.distributed import dryrun_epoch
    from repro.launch.mesh import make_production_mesh
    mesh = make_production_mesh()
    rep = dryrun_epoch(mesh, dim=dim, columns=columns, n_samples=samples)
    out = {"arch": "memhd-qail", "shape": f"{dim}x{columns}x{samples}",
           "mesh": "16x16", "status": "ok", "step": "memhd", **rep}
    d = os.path.join(os.path.dirname(__file__), "..", "reports", "dryrun")
    os.makedirs(d, exist_ok=True)
    fn = os.path.join(d, f"memhd-qail__{dim}x{columns}x{samples}__16x16.json")
    with open(fn, "w") as f:
        json.dump(out, f, indent=1, default=str)
    return out


def bench_memhd_cell() -> None:
    """Registered-bench path: the memhd cell in a fresh interpreter.

    Reduced geometry (256x256, 8192 samples) — the cell only lowers and
    compiles (roofline cost model, no training), so this is a compile
    benchmark; the JSON summary the subprocess prints becomes the row's
    derived metrics.
    """
    from benchmarks.common import row

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(root, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    # A compile dry run by design: the child must never ask for the
    # chip, which this process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks.hillclimb", "--memhd",
           "--dim", "256", "--columns", "256", "--samples", "8192"]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=root)
    elapsed_us = (time.perf_counter() - t0) * 1e6
    if proc.returncode != 0:
        raise RuntimeError(
            f"hillclimb memhd subprocess failed "
            f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
    stdout = proc.stdout
    rep = json.loads(stdout[stdout.index("{"):])
    row("hillclimb_memhd_256x256", elapsed_us,
        f"dominant={rep['dominant']} mfu_bound={rep['mfu_bound']:.3f}",
        t_compute=rep["t_compute"], t_memory=rep["t_memory"],
        t_collective=rep["t_collective"], useful=rep["useful"],
        mfu_bound=rep["mfu_bound"], live_gb=rep["live_GB"])


def main(argv=None):
    # benchmarks.run calls main() with no args: run the registered
    # bench path (NOT sys.argv, which would be run.py's own flags).
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default=None)
    ap.add_argument("--memhd", action="store_true")
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--columns", type=int, default=1024)
    ap.add_argument("--samples", type=int, default=61_440)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args([] if argv is None else argv)
    if args.list:
        for k in VARIANTS:
            print(k)
        return
    if args.memhd:
        rep = run_memhd(args.dim, args.columns, args.samples)
    elif args.variant is None:
        bench_memhd_cell()
        return
    else:
        rep = run_variant(args.variant)
    r = rep["roofline"]
    print(json.dumps({
        "variant": args.variant or "memhd",
        "status": rep.get("status"),
        "t_compute": r["t_compute"], "t_memory": r["t_memory"],
        "t_collective": r["t_collective"], "dominant": r["dominant"],
        "useful": r["useful_flops_ratio"], "mfu_bound": r["mfu_bound"],
        "wire_by_kind_GB": {k: round(v / 1e9, 1)
                            for k, v in r["wire_by_kind"].items()},
        "live_GB": round((rep["memory"]["argument_bytes"]
                          + rep["memory"]["temp_bytes"]
                          - rep["memory"].get("alias_bytes", 0)) / 1e9, 1),
        "grad_accum": rep.get("grad_accum"),
    }, indent=1))


if __name__ == "__main__":
    main(sys.argv[1:])
