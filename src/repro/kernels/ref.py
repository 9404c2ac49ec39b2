"""Pure-jnp oracles for every Pallas kernel in this package.

These are the *semantics* — the kernels must match them bit-for-bit (exact
integer-valued arithmetic) across the shape/dtype sweeps in
tests/test_kernels.py. Keep them boring and obviously correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def binary_mvm(x: Array, w: Array) -> Array:
    """H = x @ w with float32 accumulation, at float32 precision.

    x: (B, K) features or queries; w: (K, N) bipolar projection/AM weights.
    """
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def am_search(q: Array, am_t: Array) -> tuple[Array, Array]:
    """Fused associative search.

    q: (B, D) queries; am_t: (D, C) transposed AM (column c = centroid c).

    Returns:
      (best_idx, best_sim): (B,) int32 argmax centroid (first-wins ties,
      matching the kernel's running-compare semantics) and (B,) float32
      max similarity.
    """
    sims = jnp.dot(q.astype(jnp.float32), am_t.astype(jnp.float32),
                   preferred_element_type=jnp.float32)  # (B, C)
    best_idx = jnp.argmax(sims, axis=-1).astype(jnp.int32)
    best_sim = jnp.max(sims, axis=-1)
    return best_idx, best_sim


def pack_bits(x: Array) -> Array:
    """Pack bipolar/binary values into uint8, 8 cells per byte, LSB-first.

    x: (R, C) with C % 8 == 0; a cell is "1" iff x > 0.

    Returns: (R, C // 8) uint8.
    """
    r, c = x.shape
    bits = (x > 0).astype(jnp.int32).reshape(r, c // 8, 8)
    weights = (2 ** jnp.arange(8, dtype=jnp.int32))
    return jnp.sum(bits * weights, axis=-1).astype(jnp.uint8)


def unpack_bits(packed: Array, dtype=jnp.float32) -> Array:
    """Inverse of pack_bits: (R, C//8) uint8 -> (R, C) bipolar {-1, +1}."""
    r, cb = packed.shape
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = (packed.astype(jnp.int32)[:, :, None] >> shifts) & 1
    return (bits.reshape(r, cb * 8).astype(dtype) * 2 - 1)


def pack_rows(x: Array) -> Array:
    """(B, D) bipolar -> (B, ceil(D/8)) uint8; tail bits packed as 0."""
    d = x.shape[-1]
    pad = -d % 8
    if pad:
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad)),
                    constant_values=-1.0)
    return pack_bits(x)


def hamming_distances(q_packed: Array, am_packed_t: Array) -> Array:
    """Popcount(XOR) distances over packed bits.

    q_packed: (B, Dp) uint8; am_packed_t: (Dp, C) uint8 -> (B, C) int32.
    """
    x = jax.lax.bitwise_xor(
        q_packed.astype(jnp.int32)[:, :, None],
        am_packed_t.astype(jnp.int32)[None, :, :])  # (B, Dp, C)
    v = x - ((x >> 1) & 0x55)
    v = (v & 0x33) + ((v >> 2) & 0x33)
    pc = (v + (v >> 4)) & 0x0F
    return jnp.sum(pc, axis=1)


def am_search_packed(q_packed: Array, am_packed_t: Array, n_dims: int,
                     ) -> tuple[Array, Array]:
    """Packed-domain associative search oracle.

    Uses the bipolar identity dot = D - 2*hamming (tail bits pack to 0 in
    both operands, so they cancel in the XOR). Returns the same
    (best_idx, best_sim) as ``am_search`` on the unpacked operands.
    """
    ham = hamming_distances(q_packed, am_packed_t)  # (B, C)
    sims = (n_dims - 2 * ham).astype(jnp.float32)
    best_idx = jnp.argmax(sims, axis=-1).astype(jnp.int32)
    best_sim = jnp.max(sims, axis=-1)
    return best_idx, best_sim


def _rank_by_sim_then_id(sims: Array, ids: Array) -> Array:
    """Column order sorting each row by (-sim, id): best similarity
    first, ties broken toward the LOWER id — exactly the flat kernel's
    first-wins running compare when ids are the original scan order.

    Implemented as a two-pass stable sort (sort by id, then stably by
    -sim), which is the lexicographic (-sim, id) order.
    """
    id_order = jnp.argsort(ids, axis=-1, stable=True)
    sims_by_id = jnp.take_along_axis(sims, id_order, axis=-1)
    sim_order = jnp.argsort(-sims_by_id, axis=-1, stable=True)
    return jnp.take_along_axis(id_order, sim_order, axis=-1)


def am_shortlist(q_packed: Array, super_packed_t: Array, n_dims: int,
                 s: int) -> tuple[Array, Array]:
    """Coarse pass of the hierarchical search: top-``s`` clusters.

    q_packed: (B, Dp) uint8 packed queries; super_packed_t: (Dp, G)
    uint8 packed super-centroids (one column per cluster of the full
    AM); n_dims: true D; s: shortlist length, 1 <= s <= G.

    Returns (cluster_idx, cluster_sims): (B, s) int32 cluster ids and
    (B, s) float32 super-centroid similarities, ordered best-first with
    ties broken toward the lower cluster id.
    """
    ham = hamming_distances(q_packed, super_packed_t)  # (B, G)
    sims = (n_dims - 2 * ham).astype(jnp.float32)
    g = sims.shape[-1]
    ids = jnp.broadcast_to(jnp.arange(g, dtype=jnp.int32), sims.shape)
    order = _rank_by_sim_then_id(sims, ids)[:, :s]
    return (order.astype(jnp.int32),
            jnp.take_along_axis(sims, order, axis=-1))


def am_search_topk(q_packed: Array, am_packed_t: Array, n_dims: int,
                   k: int) -> tuple[Array, Array]:
    """Exact flat top-k associative search (the recall reference).

    Same operands as ``am_search_packed``; returns (idx, sims), each
    (B, k), ordered by (-sim, centroid id). Row k=1 is bit-identical to
    ``am_search_packed`` (first-wins tie == lowest-id tie).
    """
    ham = hamming_distances(q_packed, am_packed_t)  # (B, C)
    sims = (n_dims - 2 * ham).astype(jnp.float32)
    c = sims.shape[-1]
    ids = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), sims.shape)
    order = _rank_by_sim_then_id(sims, ids)[:, :k]
    return (order.astype(jnp.int32),
            jnp.take_along_axis(sims, order, axis=-1))


def am_search_sparse(q_packed: Array, tiles_packed: Array,
                     tile_ids: Array, n_dims: int, k: int,
                     ) -> tuple[Array, Array]:
    """Fine pass of the hierarchical search, on pre-gathered tiles.

    q_packed: (B, Dp) uint8 packed queries; tiles_packed: (B, Dp, T*128)
    uint8 — each query's shortlisted AM tiles gathered side by side;
    tile_ids: (B, T*128) int32 ORIGINAL centroid id per gathered column
    (-1 for cluster-padding / null-tile columns).

    Returns (idx, sims): (B, k) int32 original centroid ids and (B, k)
    float32 similarities, ordered by (-sim, id); slots with no valid
    candidate left emit id -1 and sim float32-min. Tie-breaking on the
    ORIGINAL id makes the degenerate shortlist-everything configuration
    bit-exact with the flat packed scan.
    """
    # Stay in uint8 until the reduce: the (B, Dp, TC) intermediate is
    # the dominant cost of this path (it also serves as the CPU/GPU
    # serving path via ops' auto-dispatch, not just the test oracle),
    # and hardware popcount on uint8 is bit-identical to the SWAR form.
    x = jax.lax.bitwise_xor(q_packed[:, :, None], tiles_packed)
    ham = jnp.sum(jnp.bitwise_count(x), axis=1, dtype=jnp.int32)  # (B, TC)
    neg = jnp.finfo(jnp.float32).min
    valid = tile_ids >= 0
    sims = jnp.where(valid, (n_dims - 2 * ham).astype(jnp.float32), neg)
    sent = jnp.iinfo(jnp.int32).max
    ids = jnp.where(valid, tile_ids, sent)
    order = _rank_by_sim_then_id(sims, ids)[:, :k]
    top_sims = jnp.take_along_axis(sims, order, axis=-1)
    top_ids = jnp.take_along_axis(tile_ids, order, axis=-1)
    idx = jnp.where(top_sims > neg, top_ids, -1).astype(jnp.int32)
    if idx.shape[-1] < k:  # k > candidate columns: pad exhausted slots
        pad = ((0, 0), (0, k - idx.shape[-1]))
        idx = jnp.pad(idx, pad, constant_values=-1)
        top_sims = jnp.pad(top_sims, pad, constant_values=neg)
    return idx, top_sims


def encode_pack(feats: Array, projection: Array) -> Array:
    """Staged feature->packed-query chain: the ``encode_fused`` oracle.

    H = feats @ projection (float32 accumulation), binarized with the
    inference-path semantics (sign(0) -> +1, i.e. bit 1 iff H >= 0) and
    packed LSB-first along D with tail bits 0 (``pack_rows``).

    feats: (B, f); projection: (f, D) bipolar. Returns (B, ceil(D/8))
    uint8.
    """
    h = binary_mvm(feats, projection)
    q = jnp.where(h >= 0, 1.0, -1.0)
    return pack_rows(q)


def predict_from_features(feats: Array, projection: Array,
                          am_packed_t: Array, centroid_class: Array,
                          ) -> Array:
    """Staged feature->class pipeline oracle: encode_pack + packed search
    + ownership gather. Returns (B,) int32 predicted classes."""
    qp = encode_pack(feats, projection)
    idx, _ = am_search_packed(qp, am_packed_t, projection.shape[1])
    return centroid_class[idx]


def adc_quantize(x: Array, bits: int, clip: float) -> Array:
    """Symmetric mid-tread ADC transfer function.

    Clips to [-clip, +clip] and rounds to the nearest of the 2^bits + 1
    codes spaced ``step = 2*clip / 2**bits`` apart (jnp.round semantics:
    ties to even, matching the kernel bit-for-bit). With a power-of-two
    clip the step is a power of two, so any integer input with
    ``|x| <= clip`` is reproduced exactly once ``step <= 1``.
    """
    step = 2.0 * clip / (2 ** bits)
    x = jnp.clip(x, -clip, clip)
    return jnp.round(x / step) * step


def am_search_imc(q: Array, am_t: Array, *, tile_rows: int, tile_cols: int,
                  adc_bits: int, adc_clip: float,
                  offsets: Array | None = None) -> tuple[Array, Array]:
    """Tiled analog associative-search oracle (device-fidelity semantics).

    The AM is split into (tile_rows x tile_cols) physical arrays; each
    array contributes an analog partial sum that picks up its per-tile
    readout offset, goes through the ADC (``adc_quantize``), and only
    then is accumulated digitally across row-tiles. Argmax is first-wins
    over the quantized similarities.

    q: (B, D) queries; am_t: (D, C) transposed (possibly perturbed) AM;
    offsets: optional (ceil(D/tile_rows), ceil(C/tile_cols)) per-tile
    readout offsets. Returns (best_idx, best_sim) like ``am_search``.
    """
    b, d = q.shape
    d2, c = am_t.shape
    assert d == d2, (q.shape, am_t.shape)
    gd = -(-d // tile_rows)
    gc = -(-c // tile_cols)
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, gd * tile_rows - d)))
    ap = jnp.pad(am_t.astype(jnp.float32),
                 ((0, gd * tile_rows - d), (0, gc * tile_cols - c)))
    qr = qp.reshape(b, gd, tile_rows)
    ar = ap.reshape(gd, tile_rows, gc, tile_cols)
    # One (g, h) slot == one physical array's analog MVM output.
    part = jnp.einsum("bgr,grhc->bghc", qr, ar,
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
    if offsets is not None:
        part = part + offsets[None, :, :, None]
    part = adc_quantize(part, adc_bits, adc_clip)
    sims = jnp.sum(part, axis=1).reshape(b, gc * tile_cols)[:, :c]
    best_idx = jnp.argmax(sims, axis=-1).astype(jnp.int32)
    best_sim = jnp.max(sims, axis=-1)
    return best_idx, best_sim


def multibit_adc_clip(cell_bits: int, tile_rows: int = 128) -> float:
    """Default ADC full-scale range for bit-sliced multi-bit readout.

    A (tile_rows)-row analog pass over ``cell_bits``-bit cells produces
    code-domain partial sums bounded by ``Qmax * tile_rows`` with
    ``Qmax = 2**(cell_bits-1) - 1``; the default clip is the next power
    of two at or above that bound, so (as with the 1-bit kernel's
    ``clip = rows`` default) the mid-tread step is a power of two and
    integer partial sums reproduce exactly whenever ``step <= 1``.
    """
    qmax = 2 ** (cell_bits - 1) - 1
    bound = max(qmax * tile_rows, 1)
    return float(2 ** (bound - 1).bit_length())


def pack_planes(u: Array, n_planes: int) -> Array:
    """(C, D) unsigned integer codes -> (n_planes, ceil(D/8), C) uint8.

    Bit plane p holds bit p of every code, packed 8 cells/byte LSB-first
    along D (the ``pack_bits`` layout) and transposed to the kernels'
    column-major centroid placement. D-tail bits pack as 0, i.e. code 0.
    """
    c, d = u.shape
    pad = -d % 8
    u = jnp.pad(u.astype(jnp.int32), ((0, 0), (0, pad)))
    dp = u.shape[1] // 8
    weights = 2 ** jnp.arange(8, dtype=jnp.int32)
    planes = []
    for p in range(n_planes):
        bits = ((u >> p) & 1).reshape(c, dp, 8)
        planes.append(jnp.sum(bits * weights, axis=-1).astype(jnp.uint8).T)
    return jnp.stack(planes)


def unpack_planes(planes: Array) -> Array:
    """Inverse of ``pack_planes``: (P, Dp, C) uint8 -> (Dp*8, C) int32
    offset codes (D-tail rows unpack to 0)."""
    n_planes, dp, c = planes.shape
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = (planes.astype(jnp.int32)[:, :, None, :]
            >> shifts[None, None, :, None]) & 1       # (P, Dp, 8, C)
    weights = 2 ** jnp.arange(n_planes, dtype=jnp.int32)
    return jnp.sum(bits.reshape(n_planes, dp * 8, c)
                   * weights[:, None, None], axis=0)


def am_search_multibit(q: Array, am_planes_t: Array, *, cell_bits: int,
                       tile_rows: int = 128, tile_cols: int = 128,
                       adc_bits: int = 16,
                       adc_clip: float | None = None,
                       offsets: Array | None = None,
                       ) -> tuple[Array, Array]:
    """Bit-sliced multi-bit associative-search oracle (code domain).

    The resident AM is ``cell_bits``-bit symmetric codes stored as
    offset codes ``u = code + Qmax`` in ``pack_planes`` bit planes;
    the search unpacks them, recenters (``code = u - Qmax``), and runs
    the same tiled analog-partial-sum + ADC + first-wins pipeline as
    ``am_search_imc`` — in the integer code domain, so every similarity
    is integer-valued and the kernel must match bit for bit. Callers
    wanting dequantized similarities multiply by the AM scale.

    q: (B, D) bipolar queries; am_planes_t: (cell_bits, ceil(D/8), C)
    uint8 bit planes; offsets: optional (ceil(D/tile_rows),
    ceil(C/tile_cols)) per-tile code-domain readout offsets.
    Returns (best_idx, best_sim) like ``am_search``.
    """
    if adc_clip is None:
        adc_clip = multibit_adc_clip(cell_bits, tile_rows)
    qmax = 2 ** (cell_bits - 1) - 1
    b, d = q.shape
    n_planes, dp, c = am_planes_t.shape
    assert n_planes == cell_bits, (am_planes_t.shape, cell_bits)
    assert dp * 8 >= d > (dp - 1) * 8, (q.shape, am_planes_t.shape)
    # Recentered codes; D-tail cells read -Qmax, but the matching query
    # rows are zero-padded so they contribute nothing (the kernel's
    # rowsum correction has the same property).
    codes_t = (unpack_planes(am_planes_t) - qmax).astype(jnp.float32)
    gd = -(-dp * 8 // tile_rows)
    gc = -(-c // tile_cols)
    qp = jnp.pad(q.astype(jnp.float32),
                 ((0, 0), (0, gd * tile_rows - d)))
    ap = jnp.pad(codes_t, ((0, gd * tile_rows - dp * 8),
                           (0, gc * tile_cols - c)))
    qr = qp.reshape(b, gd, tile_rows)
    ar = ap.reshape(gd, tile_rows, gc, tile_cols)
    part = jnp.einsum("bgr,grhc->bghc", qr, ar,
                      preferred_element_type=jnp.float32)
    if offsets is not None:
        part = part + offsets[None, :, :, None]
    part = adc_quantize(part, adc_bits, adc_clip)
    sims = jnp.sum(part, axis=1).reshape(b, gc * tile_cols)[:, :c]
    best_idx = jnp.argmax(sims, axis=-1).astype(jnp.int32)
    best_sim = jnp.max(sims, axis=-1)
    return best_idx, best_sim


def qail_update_delta(q: Array, upd: Array, am_t: Array,
                      centroid_class: Array, labels: Array, mask: Array,
                      lr: float) -> tuple[Array, Array]:
    """Fused QAIL inner step (§III-C steps 1-3) for one minibatch.

    q: (B, D) binarized queries; upd: (B, D) Eq.-(6) update payload;
    am_t: (D, C) transposed binary AM; centroid_class: (C,) ownership;
    labels: (B,) int labels (-1 for padded rows); mask: (B,) {0,1}.

    Returns (delta, n_miss): delta is the (C, D) float32 Eq.-(6) AM
    increment, expressed as the one-hot selection matmul
    ``W^T @ upd`` with W[i] = lr*mis_i*(onehot(true_t_i)-onehot(pred_t_i))
    — the formulation the Pallas kernel computes on the MXU, so kernel
    and oracle share bit-identical arithmetic.
    """
    c = am_t.shape[1]
    sims = jnp.dot(q.astype(jnp.float32), am_t.astype(jnp.float32),
                   preferred_element_type=jnp.float32)  # (B, C)
    pred_t = jnp.argmax(sims, axis=-1)  # Eq. (4)
    pred_class = centroid_class[pred_t]
    mis = (pred_class != labels).astype(jnp.float32) * mask

    neg = jnp.finfo(sims.dtype).min
    own = centroid_class[None, :] == labels[:, None]
    true_t = jnp.argmax(jnp.where(own, sims, neg), axis=-1)  # Eq. (5)

    w = (lr * mis)[:, None] * (
        jax.nn.one_hot(true_t, c, dtype=jnp.float32)
        - jax.nn.one_hot(pred_t, c, dtype=jnp.float32))  # (B, C)
    delta = jnp.dot(w.T, upd.astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)  # (C, D)
    return delta, mis.sum()
