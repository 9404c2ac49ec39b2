"""Search-over-tilings autotuner for the MEMHD hot-path kernels.

``am_search_packed``, ``encode_pack`` (the fused encoder),
``qail_update``, and the two hierarchical-search kernels
(``am_shortlist``, ``am_search_sparse``) ship with a fixed
batch-tile height (``block_b``)
chosen for the paper's flagship 128x128 geometry. The lane/sublane tile
(``TILE = 128``) is NOT searchable — it IS the IMC-array contract
(kernel grid == ``repro.core.imc`` cycle count, asserted in tests) —
but ``block_b`` is a free VMEM-residency knob: it sets how many query
rows each grid step holds resident (scratch accumulators, the XOR
broadcast of the popcount path, the one-hot selection matmul of the
QAIL step), trading fewer grid steps against a larger VMEM footprint.
MIMHD-style frontier work (PAPERS.md) shows the efficiency frontier is
tiling-sensitive; this module searches it instead of hardcoding it.

For each kernel the tuner:

  1. builds deterministic inputs for the requested geometry,
  2. walks the kernel's ``TUNE_BLOCK_B`` candidate list, skipping any
     candidate whose estimated per-step VMEM footprint exceeds the
     budget (``--vmem-budget-mb``, default 8 MB of the ~16 MB/core),
  3. parity-checks every candidate bit-exactly against the ``ref.py``
     oracle BEFORE timing it (a tiling that changes results is a bug,
     never a win — ``block_b`` only re-tiles the batch axis, so outputs
     must be identical),
  4. times the real dispatch path (Pallas; interpret mode off-TPU,
     where per-grid-step overhead still orders block sizes the same
     way: fewer batch steps = fewer dispatched tiles) and caches the
     winner per (kernel, backend, geometry) in a JSON config cache.

``ops.py`` dispatch consults the cache (``tuned_block_b``) whenever the
caller doesn't pin ``block_b`` explicitly, falling back to the kernel's
``DEFAULT_BLOCK_B``; the committed cache ships tuned entries for the
paper geometries. Re-tune after changing a kernel or geometry with:

    PYTHONPATH=src python -m repro.kernels.autotune --kernel all

The cache lives next to this file (``autotune_cache.json``); point
``$MEMHD_AUTOTUNE_CACHE`` elsewhere to experiment without touching the
committed configs. Tuned-vs-default bit-exactness and the cache
round-trip are covered in tests/test_bench_harness.py; the recorded
tuned-vs-default microbench lives in benchmarks/kernel_bench.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import am_search_multibit as _amb
from repro.kernels import am_search_packed as _asp
from repro.kernels import am_search_sparse as _ass
from repro.kernels import am_shortlist as _shl
from repro.kernels import encode_fused as _ef
from repro.kernels import qail_update as _qu
from repro.kernels import ref

SCHEMA_VERSION = 1
CACHE_ENV = "MEMHD_AUTOTUNE_CACHE"
DEFAULT_CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "autotune_cache.json")
DEFAULT_VMEM_BUDGET_MB = 8.0
TILE = 128


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One tunable kernel: geometry key dims, candidates, runners."""

    name: str
    key_dims: Tuple[str, ...]          # geometry dims identifying a config
    default_block_b: int
    candidates: Tuple[int, ...]
    make_inputs: Callable             # (rng, batch, dims) -> args tuple
    run: Callable                     # (block_b, *args) -> outputs
    run_ref: Callable                 # (*args) -> oracle outputs
    vmem_bytes: Callable              # (block_b, dims) -> int estimate


def _asp_inputs(rng, batch, dims):
    d, c = dims["D"], dims["C"]
    q = jnp.asarray(rng.choice([-1.0, 1.0], size=(batch, d))
                    .astype(np.float32))
    am = jnp.asarray(rng.choice([-1.0, 1.0], size=(c, d))
                     .astype(np.float32))
    return ref.pack_rows(q), ref.pack_rows(am).T, d


def _packed_block_vmem(bb, d, per_query_am: bool = False):
    """Double-buffered (bb, P) query and (P, TILE) AM byte blocks — the
    AM block is per query, (bb, P, TILE), for the sparse kernel — plus
    the (ROWS, P, TILE) int32 XOR broadcast and the f32 accumulator."""
    p = _asp.dp_block(-(-d // 8))
    am = bb * p * TILE if per_query_am else p * TILE
    return (2 * (bb * p + am) + _asp.ROWS * p * TILE * 4
            + bb * TILE * 4)


def _asp_vmem(bb, dims):
    # Packed blocks + accumulator, plus the winner scratch.
    return _packed_block_vmem(bb, dims["D"]) + bb * 8


def _ef_inputs(rng, batch, dims):
    f, d = dims["f"], dims["D"]
    feats = jnp.asarray(rng.random((batch, f)).astype(np.float32))
    proj = jnp.asarray(rng.choice([-1.0, 1.0], size=(f, d))
                       .astype(np.float32))
    return feats, proj


def _ef_vmem(bb, dims):
    # x and w blocks (double-buffered) + f32 accumulator + packed out.
    td = min(-(-dims["D"] // TILE) * TILE, _ef.MAX_TD)
    return 2 * (bb * TILE * 4 + TILE * td * 4) + bb * td * 4 + bb * td // 8


def _qu_inputs(rng, batch, dims):
    d, c = dims["D"], dims["C"]
    q = jnp.asarray(rng.choice([-1.0, 1.0], size=(batch, d))
                    .astype(np.float32))
    upd = jnp.asarray(rng.choice([-1.0, 1.0], size=(batch, d))
                      .astype(np.float32))
    am_t = jnp.asarray(rng.choice([-1.0, 1.0], size=(d, c))
                       .astype(np.float32))
    own = jnp.asarray(rng.integers(0, max(dims.get("classes", 10), 1),
                                   size=(c,)).astype(np.int32))
    labels = jnp.asarray(rng.integers(
        0, max(dims.get("classes", 10), 1), size=(batch,))
        .astype(np.int32))
    mask = jnp.ones((batch,), jnp.float32)
    return q, upd, am_t, own, labels, mask


def _qu_vmem(bb, dims):
    d = -(-dims["D"] // TILE) * TILE
    c = -(-dims["C"] // TILE) * TILE
    # q + upd blocks, resident AM, resident (C, D) delta, (bb, C) sims/W.
    return 2 * bb * d * 4 + d * c * 4 + c * d * 4 + 2 * bb * c * 4


def _amb_inputs(rng, batch, dims):
    # A quantized float AM packed into offset-code bit planes (inline
    # quantizer — keeps this module kernels-only, no repro.core import).
    d, c, bits = dims["D"], dims["C"], dims["bits"]
    qmax = 2 ** (bits - 1) - 1
    fp = rng.normal(size=(c, d)).astype(np.float32)
    scale = np.abs(fp).max() / qmax
    codes = np.clip(np.round(fp / scale), -qmax, qmax).astype(np.int32)
    planes = ref.pack_planes(jnp.asarray(codes + qmax), bits)
    q = jnp.asarray(rng.choice([-1.0, 1.0], size=(batch, d))
                    .astype(np.float32))
    return q, planes, bits


def _amb_vmem(bb, dims):
    # q block + the per-plane unpacked {0,1} slab + int32 bit broadcast
    # + partial/accumulator blocks and winner scratch.
    return (bb * TILE * 4 + TILE * TILE * 4 + TILE * TILE * 4
            + 2 * bb * TILE * 4 + bb * 8)


def _shl_inputs(rng, batch, dims):
    d, g, s = dims["D"], dims["G"], dims["S"]
    q = jnp.asarray(rng.choice([-1.0, 1.0], size=(batch, d))
                    .astype(np.float32))
    am = jnp.asarray(rng.choice([-1.0, 1.0], size=(g, d))
                     .astype(np.float32))
    return ref.pack_rows(q), ref.pack_rows(am).T, d, s


def _shl_vmem(bb, dims):
    # Packed blocks + accumulator + the (bb, S + TILE) top-S merge pair.
    s = dims["S"]
    return _packed_block_vmem(bb, dims["D"]) + 2 * bb * (s + TILE) * 8


def _ass_inputs(rng, batch, dims):
    # Tunes the Pallas half (the gathered-tiles scan): inputs mimic the
    # XLA gather's output — per-query tile slabs with unique original
    # ids and an invalid (id -1) padding run, shared across the batch.
    d, t, k = dims["D"], dims["T"], dims["K"]
    tc = t * TILE
    cols = jnp.asarray(rng.choice([-1.0, 1.0], size=(tc, d))
                       .astype(np.float32))
    q = jnp.asarray(rng.choice([-1.0, 1.0], size=(batch, d))
                    .astype(np.float32))
    ids = rng.permutation(4 * tc)[:tc].astype(np.int32)
    ids[tc - TILE // 2:] = -1
    qp = ref.pack_rows(q)
    tiles = jnp.broadcast_to(ref.pack_rows(cols).T[None, :, :],
                             (batch, qp.shape[1], tc))
    ids_b = jnp.broadcast_to(jnp.asarray(ids)[None, :], (batch, tc))
    return qp, tiles, ids_b, d, k


def _ass_vmem(bb, dims):
    # Per-query packed tile blocks + accumulator + the (bb, K + TILE)
    # top-k merge pair.
    k = dims["K"]
    return (_packed_block_vmem(bb, dims["D"], per_query_am=True)
            + 2 * bb * (k + TILE) * 8)


KERNELS: Dict[str, KernelSpec] = {
    "am_search_multibit": KernelSpec(
        name="am_search_multibit",
        key_dims=("D", "C", "bits"),
        default_block_b=_amb.DEFAULT_BLOCK_B,
        candidates=_amb.TUNE_BLOCK_B,
        make_inputs=_amb_inputs,
        run=lambda bb, q, planes, bits: _amb.am_search_multibit(
            q, planes, cell_bits=bits, block_b=bb),
        run_ref=lambda q, planes, bits: ref.am_search_multibit(
            q, planes, cell_bits=bits),
        vmem_bytes=_amb_vmem,
    ),
    "am_search_packed": KernelSpec(
        name="am_search_packed",
        key_dims=("D", "C"),
        default_block_b=_asp.DEFAULT_BLOCK_B,
        candidates=_asp.TUNE_BLOCK_B,
        make_inputs=_asp_inputs,
        run=lambda bb, qp, apt, d: _asp.am_search_packed(
            qp, apt, n_dims=d, block_b=bb),
        run_ref=lambda qp, apt, d: ref.am_search_packed(qp, apt, d),
        vmem_bytes=_asp_vmem,
    ),
    "am_shortlist": KernelSpec(
        name="am_shortlist",
        key_dims=("D", "G", "S"),
        default_block_b=_shl.DEFAULT_BLOCK_B,
        candidates=_shl.TUNE_BLOCK_B,
        make_inputs=_shl_inputs,
        run=lambda bb, qp, spt, d, s: _shl.am_shortlist(
            qp, spt, n_dims=d, s=s, block_b=bb),
        run_ref=lambda qp, spt, d, s: ref.am_shortlist(qp, spt, d, s),
        vmem_bytes=_shl_vmem,
    ),
    "am_search_sparse": KernelSpec(
        name="am_search_sparse",
        key_dims=("D", "T", "K"),
        default_block_b=_ass.DEFAULT_BLOCK_B,
        candidates=_ass.TUNE_BLOCK_B,
        make_inputs=_ass_inputs,
        run=lambda bb, qp, tiles, ids, d, k: _ass.am_search_sparse_gathered(
            qp, tiles, ids, n_dims=d, k=k, block_b=bb),
        run_ref=lambda qp, tiles, ids, d, k: ref.am_search_sparse(
            qp, tiles, ids, d, k),
        vmem_bytes=_ass_vmem,
    ),
    "encode_pack": KernelSpec(
        name="encode_pack",
        key_dims=("f", "D"),
        default_block_b=_ef.DEFAULT_BLOCK_B,
        candidates=_ef.TUNE_BLOCK_B,
        make_inputs=_ef_inputs,
        run=lambda bb, feats, proj: _ef.encode_pack(
            feats, proj, block_b=bb),
        run_ref=lambda feats, proj: ref.encode_pack(feats, proj),
        vmem_bytes=_ef_vmem,
    ),
    "qail_update": KernelSpec(
        name="qail_update",
        key_dims=("D", "C"),
        default_block_b=_qu.DEFAULT_BLOCK_B,
        candidates=_qu.TUNE_BLOCK_B,
        make_inputs=_qu_inputs,
        # Dyadic lr: every Eq.-(6) delta term is +-2^-4 on +-1 payloads,
        # so partial sums are exact in f32 and the per-B-block
        # accumulation a block_b retiling introduces is order-exact —
        # bit-exactness vs the whole-batch oracle holds for EVERY
        # candidate. (A non-dyadic lr differs in the last ulp once
        # batch > block_b; the training engine itself never tiles —
        # its minibatches fit one block.)
        run=lambda bb, q, upd, am_t, own, y, m: _qu.qail_update(
            q, upd, am_t, own, y, m, lr=0.0625, block_b=bb),
        run_ref=lambda q, upd, am_t, own, y, m: ref.qail_update_delta(
            q, upd, am_t, own, y, m, 0.0625),
        vmem_bytes=_qu_vmem,
    ),
}

# Paper geometries tuned by default (and shipped in the committed cache).
DEFAULT_GEOMETRIES: Dict[str, Tuple[Dict[str, int], ...]] = {
    "am_search_multibit": ({"D": 128, "C": 128, "bits": 2},
                           {"D": 128, "C": 128, "bits": 4}),
    "am_search_packed": ({"D": 128, "C": 128}, {"D": 256, "C": 256}),
    # Hierarchical search: one serving-scale geometry (the 128x128
    # flagship model under the default G ~ 1.4*sqrt(C)) and one
    # huge-label geometry matching the C=100k serving recommendation of
    # the benchmarks/hierarchical_search.py sweep (G=448, S=8, balanced
    # layout max_tiles=2 -> T = S*max_tiles = 16).
    "am_shortlist": ({"D": 128, "G": 16, "S": 8},
                     {"D": 1024, "G": 448, "S": 8}),
    "am_search_sparse": ({"D": 128, "T": 8, "K": 1},
                         {"D": 1024, "T": 16, "K": 1}),
    "encode_pack": ({"f": 784, "D": 128}, {"f": 617, "D": 512}),
    "qail_update": ({"D": 128, "C": 128}, {"D": 256, "C": 64}),
}


def geometry_key(kernel: str, **dims) -> str:
    """Canonical geometry key, batch-agnostic: block_b clamps to the
    batch at dispatch, so one entry serves every batch size."""
    spec = KERNELS[kernel]
    missing = [k for k in spec.key_dims if k not in dims]
    if missing:
        raise KeyError(f"{kernel} geometry needs dims {spec.key_dims}, "
                       f"missing {missing}")
    return "_".join(f"{k}{int(dims[k])}" for k in spec.key_dims)


def cache_path() -> str:
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE


_LOAD_MEMO: Dict[Tuple[str, int], Dict] = {}


def load_cache(path: Optional[str] = None) -> Dict[str, Dict]:
    """The cache's entries dict; memoized per (path, mtime) so the jit
    trace-time lookups in ops.py never re-read an unchanged file."""
    path = path or cache_path()
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return {}
    memo_key = (os.path.abspath(path), mtime)
    if memo_key not in _LOAD_MEMO:
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        entries = data.get("entries", {})
        if data.get("schema_version") != SCHEMA_VERSION:
            entries = {}
        if len(_LOAD_MEMO) > 16:
            _LOAD_MEMO.clear()
        _LOAD_MEMO[memo_key] = entries
    return _LOAD_MEMO[memo_key]


def save_entry(entry: Dict, path: Optional[str] = None) -> str:
    path = path or cache_path()
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        data = {}
    if data.get("schema_version") != SCHEMA_VERSION:
        data = {"schema_version": SCHEMA_VERSION, "entries": {}}
    key = f"{entry['kernel']}|{entry['backend']}|{entry['geometry']}"
    data["entries"][key] = entry
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def lookup(kernel: str, geometry: str, backend: Optional[str] = None,
           ) -> Optional[Dict]:
    backend = backend or jax.default_backend()
    return load_cache().get(f"{kernel}|{backend}|{geometry}")


def tuned_block_b(kernel: str, **dims) -> int:
    """The block_b ops.py dispatch uses: cached winner, else default."""
    spec = KERNELS[kernel]
    entry = lookup(kernel, geometry_key(kernel, **dims))
    if entry is not None:
        return int(entry["block_b"])
    return spec.default_block_b


def _time_call(fn, *args, iters: int = 3) -> float:
    """Min wall time per call in us (min is the stable tuning statistic)."""
    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _assert_parity(got, want, label: str) -> None:
    got = jax.tree.leaves(got)
    want = jax.tree.leaves(want)
    assert len(got) == len(want), label
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=label)


def autotune_kernel(kernel: str, dims: Dict[str, int], *,
                    batch: int = 512, iters: int = 3, seed: int = 0,
                    vmem_budget_mb: float = DEFAULT_VMEM_BUDGET_MB,
                    save: bool = True,
                    cache: Optional[str] = None) -> Dict:
    """Tune one kernel at one geometry; returns (and caches) the entry.

    Every candidate is parity-checked bit-exactly against the ref.py
    oracle before timing — the search can only ever trade speed, never
    results.
    """
    spec = KERNELS[kernel]
    rng = np.random.default_rng(seed)
    args = spec.make_inputs(rng, batch, dims)
    want = spec.run_ref(*args)

    budget = int(vmem_budget_mb * 1024 * 1024)
    timings: Dict[str, float] = {}
    skipped: Dict[str, int] = {}
    seen_clamped = set()
    best_bb, best_us = None, float("inf")
    for bb in spec.candidates:
        clamped = min(bb, batch)
        if clamped in seen_clamped:
            continue  # same effective tile as a smaller candidate
        seen_clamped.add(clamped)
        est = int(spec.vmem_bytes(clamped, dims))
        if est > budget:
            skipped[str(bb)] = est
            continue
        _assert_parity(spec.run(bb, *args), want,
                       f"{kernel} block_b={bb} diverged from ref oracle")
        us = _time_call(lambda *a: spec.run(bb, *a), *args, iters=iters)
        timings[str(bb)] = round(us, 1)
        if us < best_us:
            best_bb, best_us = bb, us
    if best_bb is None:
        raise RuntimeError(
            f"{kernel}: every candidate in {spec.candidates} exceeded "
            f"the {vmem_budget_mb} MB VMEM budget")

    default_us = timings.get(str(min(spec.default_block_b, batch)))
    if default_us is None:
        default_us = _time_call(
            lambda *a: spec.run(spec.default_block_b, *a), *args,
            iters=iters)
    entry = {
        "kernel": kernel,
        "backend": jax.default_backend(),
        "geometry": geometry_key(kernel, **dims),
        "dims": {k: int(v) for k, v in dims.items()},
        "block_b": int(best_bb),
        "default_block_b": spec.default_block_b,
        "tuned_batch": int(batch),
        "best_us": round(best_us, 1),
        "default_us": round(float(default_us), 1),
        "speedup_vs_default": round(float(default_us) / best_us, 3),
        "candidates_us": timings,
        "skipped_vmem": skipped,
        "vmem_budget_mb": vmem_budget_mb,
        "vmem_bytes_est": int(spec.vmem_bytes(min(best_bb, batch), dims)),
        "created_unix": int(time.time()),
    }
    if save:
        save_entry(entry, path=cache)
    return entry


def autotune_all(kernels=None, *, batch: int = 512, iters: int = 3,
                 vmem_budget_mb: float = DEFAULT_VMEM_BUDGET_MB,
                 cache: Optional[str] = None, verbose: bool = True):
    entries = []
    for kernel in kernels or KERNELS:
        for dims in DEFAULT_GEOMETRIES[kernel]:
            entry = autotune_kernel(
                kernel, dims, batch=batch, iters=iters,
                vmem_budget_mb=vmem_budget_mb, cache=cache)
            entries.append(entry)
            if verbose:
                print(f"autotune: {kernel} {entry['geometry']} -> "
                      f"block_b={entry['block_b']} "
                      f"({entry['best_us']}us, default "
                      f"block_b={entry['default_block_b']} "
                      f"{entry['default_us']}us, "
                      f"{entry['speedup_vs_default']}x)", flush=True)
    return entries


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernel", default="all",
                    choices=["all"] + sorted(KERNELS),
                    help="which kernel to tune")
    ap.add_argument("--batch", type=int, default=512,
                    help="query batch the candidates are timed at")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--vmem-budget-mb", type=float,
                    default=DEFAULT_VMEM_BUDGET_MB)
    ap.add_argument("--cache", default=None,
                    help=f"cache file (default {DEFAULT_CACHE}, or "
                         f"${CACHE_ENV})")
    args = ap.parse_args(argv)
    kernels = list(KERNELS) if args.kernel == "all" else [args.kernel]
    autotune_all(kernels, batch=args.batch, iters=args.iters,
                 vmem_budget_mb=args.vmem_budget_mb, cache=args.cache)
    print(f"autotune: cache -> {args.cache or cache_path()}")


if __name__ == "__main__":
    main()
