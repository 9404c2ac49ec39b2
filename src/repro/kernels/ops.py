"""Public jit'd surface of the kernel package.

Higher layers call these; each dispatches to the Pallas kernel (TPU, or
interpret mode elsewhere) and is validated against ``repro.kernels.ref``
across shape/dtype sweeps in tests/test_kernels.py.

The three hot-path kernels (``am_search_packed``, ``encode_pack`` and
its fused chains, ``qail_update``) accept ``block_b=None`` (the
default), meaning: consult the ``repro.kernels.autotune`` config cache
for the best batch-tile height tuned for this (kernel, backend,
geometry) and fall back to the kernel's fixed default when no tuned
entry exists. Tuned tilings only re-tile the batch axis, so every
config is bit-exact with the ``ref.py`` oracle (parity-checked at tune
time and again in tests); pass an explicit ``block_b`` to pin a tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.obs import metrics as _obs_metrics
from repro.kernels.am_search import am_search as _am_search
from repro.kernels.am_search import imc_cycles_for as search_cycles
from repro.kernels.am_search_imc import am_search_imc as _am_search_imc
from repro.kernels.am_search_imc import imc_cycles_for as imc_search_cycles
from repro.kernels.am_search_multibit import (
    am_search_multibit as _am_search_multibit,
)
from repro.kernels.am_search_multibit import (
    imc_cycles_for as multibit_search_cycles,
)
from repro.kernels.am_search_packed import am_search_packed as _am_search_packed
from repro.kernels.am_search_packed import imc_cycles_for as packed_search_cycles
from repro.kernels.am_search_packed import pack_rows as _pack_rows
from repro.kernels.am_search_sparse import am_search_sparse as _am_search_sparse
from repro.kernels.am_search_sparse import (
    expand_shortlist_tiles as _expand_shortlist_tiles,
)
from repro.kernels.am_search_sparse import gather_shortlist as _gather_shortlist
from repro.kernels.am_shortlist import am_shortlist as _am_shortlist
from repro.kernels.binary_mvm import binary_mvm as _binary_mvm
from repro.kernels.binary_mvm import imc_cycles_for as mvm_cycles
from repro.kernels.encode_fused import encode_pack as _encode_pack
from repro.kernels.encode_fused import imc_cycles_for as encode_pack_cycles
from repro.kernels.encode_fused import (
    predict_from_features as _predict_from_features,
)
from repro.kernels.encode_fused import (
    search_from_features as _search_from_features,
)
from repro.kernels.pack_bits import pack_bits as _pack_bits
from repro.kernels.pack_bits import unpack_bits as _unpack_bits
from repro.kernels.qail_update import qail_update as _qail_update

Array = jax.Array

# Every public dispatch below counts itself here, labeled with which
# execution tier actually served it:
#   pallas     — the Pallas kernel (interpret-mode emulation off-TPU)
#   xla-oracle — the bit-exact XLA fallback the auto-dispatch kernels
#                (am_shortlist / am_search_sparse) serve through off-TPU
#   ref        — the pure-jnp ref.py oracle, requested explicitly
# plus the static geometry, so a kernel silently falling off its fast
# path (or a caller churning through padded shapes) shows up in any
# metrics snapshot instead of only as latency noise. Counts increment
# when the Python dispatch runs: once per trace for jitted callers
# (i.e. per compiled specialization), per call in eager mode.
_DISPATCH = _obs_metrics.counter(
    "kernel_dispatch_total",
    "kernel dispatches by (kernel, tier, geometry)")


def _count(kernel: str, tier: str, **dims) -> None:
    geometry = ",".join(f"{k}={v}" for k, v in sorted(dims.items()))
    _DISPATCH.inc(kernel=kernel, tier=tier, geometry=geometry)


def _tier(use_kernel: bool) -> str:
    return "pallas" if use_kernel else "ref"


def dispatch_breakdown() -> dict[str, dict[str, int]]:
    """{kernel: {tier: count}} summed over geometries — the serving
    report's and bench recorder's dispatch-tier table."""
    out: dict[str, dict[str, int]] = {}
    for labels, val in _DISPATCH.series():
        k, t = labels.get("kernel", "?"), labels.get("tier", "?")
        out.setdefault(k, {})
        out[k][t] = out[k].get(t, 0) + int(val)
    return out


def tuned_block_b(kernel: str, block_b: int | None, **dims) -> int:
    """Resolve the batch tile for a dispatch: explicit arg wins, then
    the autotune cache, then the kernel's DEFAULT_BLOCK_B. Runs at
    trace time (the cache read is memoized on file mtime)."""
    if block_b is not None:
        return block_b
    from repro.kernels import autotune  # deferred: package-init cycle
    return autotune.tuned_block_b(kernel, **dims)


__all__ = [
    "encode_mvm", "encode_pack", "am_search", "am_search_imc",
    "am_search_multibit", "am_search_packed", "am_shortlist",
    "am_search_sparse",
    "search_from_features", "predict_from_features",
    "pack_bits", "unpack_bits", "pack_rows", "qail_update",
    "predict_classes", "predict_packed", "predict_imc",
    "predict_multibit",
    "search_cycles", "imc_search_cycles", "packed_search_cycles",
    "multibit_search_cycles",
    "mvm_cycles", "encode_pack_cycles", "ref", "tuned_block_b",
    "dispatch_breakdown",
]


def encode_mvm(feats: Array, projection: Array, *, use_kernel: bool = True,
               ) -> Array:
    """Projection encoding H = F @ M through the IMC-geometry kernel.

    feats: (B, f); projection: (f, D) bipolar. Returns (B, D) float32.
    """
    _count("binary_mvm", _tier(use_kernel), B=feats.shape[0],
           f=projection.shape[0], D=projection.shape[1])
    if not use_kernel:
        return ref.binary_mvm(feats, projection)
    return _binary_mvm(feats, projection)


def encode_pack(feats: Array, projection: Array, *, use_kernel: bool = True,
                block_b: int | None = None) -> Array:
    """Fused encode + sign + bitpack: (B, f) -> (B, ceil(D/8)) uint8.

    One kernel pass: the projection MVM accumulates in VMEM and emits
    sign-binarized packed query rows directly — the float hypervector
    never reaches HBM. Bit-identical to
    ``pack_rows(binarize_query(feats @ projection))``.
    """
    _count("encode_pack", _tier(use_kernel), B=feats.shape[0],
           f=projection.shape[0], D=projection.shape[1])
    if not use_kernel:
        return ref.encode_pack(feats, projection)
    bb = tuned_block_b("encode_pack", block_b,
                       f=projection.shape[0], D=projection.shape[1])
    return _encode_pack(feats, projection, block_b=bb)


def search_from_features(feats: Array, projection: Array,
                         am_packed_t: Array, *, mode: str = "popcount",
                         use_kernel: bool = True,
                         block_b: int | None = None,
                         ) -> tuple[Array, Array]:
    """Single-dispatch feature->search chain over the packed AM.

    feats: (B, f); projection: (f, D) bipolar; am_packed_t: (Dp, C)
    uint8 (``pack_am``). Returns (best_idx, best_sim) bit-exact with
    the staged encode_query -> pack_rows -> am_search_packed chain.
    """
    _count("search_from_features", _tier(use_kernel), B=feats.shape[0],
           D=projection.shape[1], C=am_packed_t.shape[1])
    if not use_kernel:
        qp = ref.encode_pack(feats, projection)
        return ref.am_search_packed(qp, am_packed_t, projection.shape[1])
    bb = tuned_block_b("encode_pack", block_b,
                       f=projection.shape[0], D=projection.shape[1])
    return _search_from_features(feats, projection, am_packed_t,
                                 mode=mode, block_b=bb)


def predict_from_features(feats: Array, projection: Array,
                          am_packed_t: Array, centroid_class: Array, *,
                          mode: str = "popcount", use_kernel: bool = True,
                          block_b: int | None = None) -> Array:
    """End-to-end §III-D prediction from raw features, one dispatch:
    fused encode/pack -> packed search -> ownership gather."""
    _count("predict_from_features", _tier(use_kernel), B=feats.shape[0],
           D=projection.shape[1], C=am_packed_t.shape[1])
    if not use_kernel:
        return ref.predict_from_features(feats, projection, am_packed_t,
                                         centroid_class)
    bb = tuned_block_b("encode_pack", block_b,
                       f=projection.shape[0], D=projection.shape[1])
    return _predict_from_features(feats, projection, am_packed_t,
                                  centroid_class, mode=mode, block_b=bb)


def am_search(queries: Array, am: Array, *, use_kernel: bool = True,
              ) -> tuple[Array, Array]:
    """Fused associative search.

    queries: (B, D); am: (C, D) bipolar centroid rows (the (D, C)
    transpose is formed here once — resident layout matches the IMC
    array's column-major centroid placement).

    Returns (best_idx, best_sim): (B,) int32, (B,) float32.
    """
    _count("am_search", _tier(use_kernel), B=queries.shape[0],
           D=queries.shape[1], C=am.shape[0])
    am_t = am.T
    if not use_kernel:
        return ref.am_search(queries, am_t)
    return _am_search(queries, am_t)


def am_search_imc(queries: Array, am: Array, *, sim, offsets: Array = None,
                  use_kernel: bool = True) -> tuple[Array, Array]:
    """Device-fidelity associative search (tiled analog MVM + ADC).

    queries: (B, D); am: (C, D) resident centroid rows — typically the
    perturbed output of ``repro.imcsim.device.perturb_am``; sim: an
    ``ImcSimConfig`` (array geometry + ADC transfer); offsets: optional
    per-tile readout drift grid.

    With an ideal sim (>=8-bit ADC at the default 128-row array, no
    perturbations) the result is bit-exact with ``am_search``.

    Returns (best_idx, best_sim): (B,) int32, (B,) float32.
    """
    _count("am_search_imc", _tier(use_kernel), B=queries.shape[0],
           D=queries.shape[1], C=am.shape[0])
    am_t = am.T
    if not use_kernel:
        return ref.am_search_imc(
            queries, am_t, tile_rows=sim.arr.rows, tile_cols=sim.arr.cols,
            adc_bits=sim.adc_bits, adc_clip=sim.clip, offsets=offsets)
    return _am_search_imc(
        queries, am_t, offsets, tile_rows=sim.arr.rows,
        tile_cols=sim.arr.cols, adc_bits=sim.adc_bits, adc_clip=sim.clip)


def am_search_multibit(queries: Array, am_planes_t: Array, *, sim=None,
                       scale: Array | None = None,
                       offsets: Array | None = None,
                       use_kernel: bool = True,
                       block_b: int | None = None) -> tuple[Array, Array]:
    """Bit-sliced associative search over the multi-bit packed AM.

    queries: (B, D) bipolar; am_planes_t: (cell_bits, Dp, C) uint8
    offset-code bit planes (``repro.core.am.pack_am_planes``); sim: an
    optional ``ImcSimConfig`` supplying array geometry + ADC transfer
    (defaults: 128x128 array, 16-bit ADC, ``ref.multibit_adc_clip``
    full scale); scale: optional quantizer scale — when given, the
    returned similarities are dequantized (idx is scale-invariant);
    offsets: optional per-tile code-domain readout drift grid.

    Returns (best_idx, best_sim): (B,) int32, (B,) float32 — the idx
    bit-exact with ``ref.am_search_multibit`` on the same operands.
    """
    cell_bits = int(am_planes_t.shape[0])
    tile_rows = sim.arr.rows if sim is not None else 128
    tile_cols = sim.arr.cols if sim is not None else 128
    adc_bits = sim.adc_bits if sim is not None else 16
    # Not sim.clip: that property defaults to the 1-bit bound (the row
    # count); multi-bit partial sums need the Qmax-scaled full scale.
    adc_clip = (sim.adc_clip
                if sim is not None and sim.adc_clip is not None
                else ref.multibit_adc_clip(cell_bits, tile_rows))
    _count("am_search_multibit", _tier(use_kernel), B=queries.shape[0],
           D=queries.shape[1], C=am_planes_t.shape[2], bits=cell_bits)
    if not use_kernel:
        idx, s = ref.am_search_multibit(
            queries, am_planes_t, cell_bits=cell_bits,
            tile_rows=tile_rows, tile_cols=tile_cols, adc_bits=adc_bits,
            adc_clip=adc_clip, offsets=offsets)
    else:
        bb = tuned_block_b("am_search_multibit", block_b,
                           D=queries.shape[1], C=am_planes_t.shape[2],
                           bits=cell_bits)
        idx, s = _am_search_multibit(
            queries, am_planes_t, offsets, cell_bits=cell_bits,
            tile_rows=tile_rows, tile_cols=tile_cols, adc_bits=adc_bits,
            adc_clip=float(adc_clip), block_b=bb)
    if scale is not None:
        s = s * jnp.asarray(scale, jnp.float32)
    return idx, s


def am_search_packed(q_packed: Array, am_packed_t: Array, *, n_dims: int,
                     mode: str = "popcount", use_kernel: bool = True,
                     block_b: int | None = None) -> tuple[Array, Array]:
    """Fused associative search over the packed 1-bit AM.

    q_packed: (B, Dp) uint8 packed queries (``pack_rows``);
    am_packed_t: (Dp, C) uint8 resident packed AM (``pack_rows(am).T``);
    n_dims: true hypervector dimension D.

    Returns (best_idx, best_sim) bit-exact with ``am_search`` on the
    corresponding unpacked operands.
    """
    _count("am_search_packed", _tier(use_kernel), B=q_packed.shape[0],
           D=n_dims, C=am_packed_t.shape[1])
    if not use_kernel:
        return ref.am_search_packed(q_packed, am_packed_t, n_dims)
    bb = tuned_block_b("am_search_packed", block_b, D=n_dims,
                       C=am_packed_t.shape[1])
    return _am_search_packed(q_packed, am_packed_t, n_dims=n_dims,
                             mode=mode, block_b=bb)


def am_shortlist(q_packed: Array, super_packed_t: Array, *, n_dims: int,
                 s: int, use_kernel: bool | None = None,
                 block_b: int | None = None) -> tuple[Array, Array]:
    """Coarse pass of the hierarchical search: top-``s`` clusters.

    q_packed: (B, Dp) uint8 packed queries; super_packed_t: (Dp, G)
    uint8 packed super-centroids. Returns ((B, s) cluster ids, (B, s)
    super similarities), best-first, ties toward the lower cluster id —
    bit-exact with ``ref.am_shortlist``. ``use_kernel=None`` (default)
    auto-dispatches like ``am_search_sparse``: Pallas on TPU, the
    bit-exact oracle elsewhere.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    _count("am_shortlist", "pallas" if use_kernel else "xla-oracle",
           B=q_packed.shape[0], D=n_dims, G=super_packed_t.shape[1],
           S=s)
    if not use_kernel:
        return ref.am_shortlist(q_packed, super_packed_t, n_dims, s)
    bb = tuned_block_b("am_shortlist", block_b, D=n_dims,
                       G=super_packed_t.shape[1], S=s)
    return _am_shortlist(q_packed, super_packed_t, n_dims=n_dims, s=s,
                         block_b=bb)


def am_search_sparse(q_packed: Array, am_slab_t: Array, col_ids: Array,
                     shortlist: Array, tile_start: Array,
                     tile_count: Array, *, n_dims: int, k: int,
                     max_tiles: int, use_kernel: bool | None = None,
                     block_b: int | None = None) -> tuple[Array, Array]:
    """Fine pass of the hierarchical search: shortlisted tiles + top-k.

    am_slab_t/col_ids/tile_start/tile_count describe the permuted
    cluster-contiguous slab (``deploy.hierarchical.build_layout``);
    shortlist: (B, S) cluster ids from ``am_shortlist``. Returns
    ((B, k) original centroid ids, (B, k) sims) ordered by (-sim, id);
    exhausted slots are (-1, float32-min). Bit-exact with
    ``ref.am_search_sparse`` on the gathered operands, and with S = G
    the k=1 column reproduces ``am_search_packed`` bit-for-bit.

    ``use_kernel=None`` (default) auto-dispatches. On TPU the Pallas
    kernel reads each query's shortlisted tiles by DMA straight from
    the resident slab (its dispatch counted with ``path=slab-dma``):
    nothing per query is gathered in XLA. Elsewhere the bit-exact XLA
    gather + oracle path serves (``path=xla-gather``), since the
    kernel's per-row tile copies are slow to emulate in interpret
    mode; the two paths are parity-tested bit-exact.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    _count("am_search_sparse", "pallas" if use_kernel else "xla-oracle",
           B=q_packed.shape[0], D=n_dims, S=shortlist.shape[1], K=k,
           path="slab-dma" if use_kernel else "xla-gather")
    if not use_kernel:
        null_tile = am_slab_t.shape[1] // 128 - 1
        tiles = _expand_shortlist_tiles(
            shortlist, tile_start, tile_count,
            max_tiles=max_tiles, null_tile=null_tile)
        gathered, ids = _gather_shortlist(am_slab_t, col_ids, tiles)
        return ref.am_search_sparse(q_packed, gathered, ids, n_dims, k)
    bb = tuned_block_b("am_search_sparse", block_b, D=n_dims,
                       T=shortlist.shape[1] * max_tiles, K=k)
    return _am_search_sparse(q_packed, am_slab_t, col_ids, shortlist,
                             tile_start, tile_count, n_dims=n_dims, k=k,
                             max_tiles=max_tiles, block_b=bb)


def pack_rows(x: Array, *, use_kernel: bool = True) -> Array:
    """(B, D) bipolar -> (B, ceil(D/8)) uint8, any D (tail bits 0)."""
    _count("pack_rows", _tier(use_kernel), B=x.shape[0], D=x.shape[1])
    if not use_kernel:
        return ref.pack_rows(x)
    return _pack_rows(x)


def pack_bits(x: Array, *, use_kernel: bool = True) -> Array:
    if not use_kernel:
        return ref.pack_bits(x)
    return _pack_bits(x)


def unpack_bits(p: Array, *, use_kernel: bool = True) -> Array:
    if not use_kernel:
        return ref.unpack_bits(p)
    return _unpack_bits(p)


def qail_update(q: Array, upd: Array, am_t: Array, centroid_class: Array,
                labels: Array, mask: Array, *, lr: float,
                use_kernel: bool = True,
                block_b: int | None = None) -> tuple[Array, Array]:
    """Fused QAIL inner step (§III-C): sims MVM + Eq. 4/5 + Eq.-(6) delta.

    q/upd: (B, D); am_t: (D, C) transposed binary AM; labels/mask: (B,).
    Returns (delta (C, D) float32, n_miss float32) — the Eq.-(6) shadow-AM
    increment for one minibatch, bit-exact between kernel and oracle.
    """
    _count("qail_update", _tier(use_kernel), B=q.shape[0],
           D=am_t.shape[0], C=am_t.shape[1])
    if not use_kernel:
        return ref.qail_update_delta(q, upd, am_t, centroid_class,
                                     labels, mask, lr)
    bb = tuned_block_b("qail_update", block_b, D=am_t.shape[0],
                       C=am_t.shape[1])
    return _qail_update(q, upd, am_t, centroid_class, labels, mask,
                        lr=lr, block_b=bb)


def predict_classes(queries: Array, am: Array, centroid_class: Array,
                    *, use_kernel: bool = True) -> Array:
    """End-to-end §III-D prediction: search + ownership lookup."""
    idx, _ = am_search(queries, am, use_kernel=use_kernel)
    return centroid_class[idx]


def predict_packed(queries: Array, am_packed_t: Array,
                   centroid_class: Array, *, n_dims: int,
                   mode: str = "popcount", use_kernel: bool = True,
                   ) -> Array:
    """§III-D prediction over the packed residence: pack the bipolar
    queries, fused XOR+popcount search, ownership lookup."""
    qp = pack_rows(queries, use_kernel=use_kernel)
    idx, _ = am_search_packed(qp, am_packed_t, n_dims=n_dims, mode=mode,
                              use_kernel=use_kernel)
    return centroid_class[idx]


def predict_imc(queries: Array, am: Array, centroid_class: Array, *,
                sim, offsets: Array = None, use_kernel: bool = True,
                ) -> Array:
    """§III-D prediction through the simulated analog readout:
    tiled analog search + ADC + ownership lookup."""
    idx, _ = am_search_imc(queries, am, sim=sim, offsets=offsets,
                           use_kernel=use_kernel)
    return centroid_class[idx]


def predict_multibit(queries: Array, am_planes_t: Array,
                     centroid_class: Array, *, sim=None,
                     offsets: Array = None, use_kernel: bool = True,
                     ) -> Array:
    """§III-D prediction over the multi-bit residence: bit-sliced
    code-domain search + ownership lookup (argmax is scale-invariant,
    so the quantizer scale never enters)."""
    idx, _ = am_search_multibit(queries, am_planes_t, sim=sim,
                                offsets=offsets, use_kernel=use_kernel)
    return centroid_class[idx]
