"""Quantization-aware iterative learning (QAIL) — §III-C.

The four steps per training sample, verbatim from the paper:

1. *Dot similarity* — similarity of the binarized query H^b against the
   **binary** AM; an update fires only on misprediction.
2. *Update target selection* — Eq. (4): the mispredicted class's centroid
   with the globally-highest similarity is the push-away target; Eq. (5):
   the true class's most-similar centroid is the pull-toward target.
3. *Iterative learning* — Eq. (6): C_true += alpha*H, C_pred -= alpha*H,
   applied to the **float** shadow AM.
4. *Binary AM update* — per-centroid normalization of the float AM (so no
   centroid dominates) followed by re-binarization (mean threshold).

Three implementations:

* ``qail_epoch_sequential`` — exact paper semantics: one sample at a time
  (``lax.scan``), the binary AM refreshed once per epoch (step 4 happens
  at epoch granularity, matching "iterative learning ... across the entire
  training dataset" + a normalization step per pass).
* ``qail_epoch_scan`` — the device-resident training engine: one
  jit-compiled ``lax.scan`` over a *pre-batched* epoch (``prebatch``),
  with the ``refresh_every`` binary-AM refresh folded into the scan as a
  ``lax.cond``. ONE dispatch and (at most) one host sync per epoch —
  this is what ``MemhdModel.fit``, ``fit_sharded`` and the fault-tolerant
  driver run. ``qail_epoch_batched`` is its convenience wrapper over
  unbatched arrays.
* ``qail_epoch_hostloop`` — the pre-refactor host-side Python loop (one
  jit dispatch + one device sync per minibatch). Kept as the measured
  baseline for ``benchmarks/train_throughput.py`` and as a parity oracle
  for the scan engine; new code should not call it.
"""
from __future__ import annotations

from functools import cache, partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import am as am_lib
from repro.core.types import MemhdConfig

Array = jax.Array
AmState = Dict[str, Array]


def _donate() -> Tuple[int, ...]:
    """Argnums of the scan epoch's donated AM state. Donation only helps
    (and only works) on accelerator backends; on CPU it just emits
    "donation not usable" warnings. Asked at call time, so importing
    this module does not initialise a backend."""
    return (0,) if jax.default_backend() in ("tpu", "gpu") else ()


# Incremented each time the scan-epoch body is *traced* (not executed).
# The single-host-sync test asserts a multi-epoch fit traces it once.
_scan_trace_count = 0


def _normalize_fp(fp_am: Array, mode: str) -> Array:
    """§III-C step 4's normalization.

    "ensures an even distribution of learning influence across multiple
    class vectors within the same class, preventing any single vector
    from dominating" — implemented as norm *equalization*: every centroid
    is rescaled to the mean centroid norm. This evens influence without
    collapsing the AM's overall scale (which must stay at sample-
    hypervector magnitude for Eq.-(6)'s lr*H updates to remain
    proportionate nudges).
    """
    if mode == "none":
        return fp_am
    if mode == "l2":
        norm = jnp.linalg.norm(fp_am, axis=-1, keepdims=True)
        mean_norm = jnp.mean(norm)
        return fp_am * (mean_norm / jnp.maximum(norm, 1e-8))
    raise ValueError(f"bad normalize mode: {mode!r}")


def select_update_targets(sims: Array, centroid_class: Array, label: Array,
                          n_classes: int) -> Tuple[Array, Array, Array]:
    """Eqs. (4) and (5) for a single query.

    Args:
      sims: (C,) dot similarities of one query against the binary AM.
      centroid_class: (C,) centroid ownership.
      label: scalar true class l.
      n_classes: k.

    Returns:
      (mispredicted, pred_target, true_target):
        mispredicted: bool scalar — fire an update?
        pred_target: centroid index (l', m) of Eq. (4) (global argmax).
        true_target: centroid index (l, n) of Eq. (5) (argmax within the
          true class).
    """
    pred_target = jnp.argmax(sims)  # Eq. (4): global best centroid
    pred_class = centroid_class[pred_target]
    mispredicted = pred_class != label

    neg = jnp.finfo(sims.dtype).min
    own = centroid_class == label
    true_target = jnp.argmax(jnp.where(own, sims, neg))  # Eq. (5)
    del n_classes
    return mispredicted, pred_target, true_target


@partial(jax.jit, static_argnames=("cfg",))
def qail_epoch_sequential(state: AmState, cfg: MemhdConfig,
                          h: Array, queries: Array, labels: Array,
                          ) -> AmState:
    """One exact (sample-by-sample) QAIL epoch.

    Args:
      state: AM state dict (fp, binary, centroid_class).
      cfg: MEMHD config (lr, normalize, threshold, update_with).
      h: (n, D) float encoded hypervectors (the Eq.-6 update payload when
        ``cfg.update_with == "encoded"``).
      queries: (n, D) binarized queries H^b (similarity payload).
      labels: (n,) int labels.

    Returns:
      Updated AM state (binary refreshed once, at epoch end — step 4).
    """
    centroid_class = state["centroid_class"]
    binary = state["binary"]
    upd = h if cfg.update_with == "encoded" else queries

    def body(fp, inputs):
        q, u, y = inputs
        sims = binary @ q  # (C,) — evaluated against the epoch's binary AM
        mis, pred_t, true_t = select_update_targets(
            sims, centroid_class, y, cfg.classes)
        delta = jnp.where(mis, cfg.lr, 0.0)
        fp = fp.at[true_t].add(delta * u)
        fp = fp.at[pred_t].add(-delta * u)
        return fp, mis

    fp, misses = jax.lax.scan(body, state["fp"], (queries, upd, labels))
    fp = _normalize_fp(fp, cfg.normalize)
    new_state = dict(state, fp=fp,
                     binary=am_lib.binarize_am(fp, cfg.threshold))
    return new_state


@partial(jax.jit, static_argnames=("cfg", "wire_dtype"))
def qail_batch_delta(state: AmState, cfg: MemhdConfig,
                     h: Array, queries: Array, labels: Array,
                     wire_dtype=jnp.bfloat16,
                     mask: Optional[Array] = None,
                     ) -> Tuple[Array, Array]:
    """Eq.-(6) update *delta* for a batch (no state mutation).

    Returns (delta, n_miss) with delta shaped like the float AM. Exposed
    separately so distributed training can control the cross-shard sync:
    ONE fused scatter (true-target and pred-target updates concatenated)
    emitted in ``wire_dtype`` — under GSPMD the all-reduce operand is the
    scatter output, so this is what sets the wire format (§Perf Q2: one
    bf16 reduce instead of two f32 ones, 8x fewer bytes).

    ``mask`` (B,) zeroes padded samples so pre-batched epochs with a
    ragged final batch (``prebatch``) stay exact.
    """
    centroid_class = state["centroid_class"]
    binary = state["binary"]
    upd = h if cfg.update_with == "encoded" else queries

    sims = queries @ binary.T  # (B, C)
    pred_t = jnp.argmax(sims, axis=-1)
    pred_class = centroid_class[pred_t]
    mis = (pred_class != labels).astype(jnp.float32)
    if mask is not None:
        mis = mis * mask

    neg = jnp.finfo(sims.dtype).min
    own = centroid_class[None, :] == labels[:, None]
    true_t = jnp.argmax(jnp.where(own, sims, neg), axis=-1)

    coef = ((cfg.lr * mis)[:, None] * upd).astype(wire_dtype)
    delta = jnp.zeros(state["fp"].shape, wire_dtype)
    delta = delta.at[true_t].add(coef)
    delta = delta.at[pred_t].add(-coef)
    return delta, mis.sum()


@partial(jax.jit, static_argnames=("cfg",))
def qail_batch_update(state: AmState, cfg: MemhdConfig,
                      h: Array, queries: Array, labels: Array,
                      ) -> Tuple[AmState, Array]:
    """Minibatched QAIL update (one batch, one binary-AM snapshot).

    All mispredicted samples in the batch compute their Eq.-(4)/(5)
    targets against the same binary AM and their Eq.-(6) deltas are
    scatter-added. Returns (new_state_without_binary_refresh, n_miss).
    """
    centroid_class = state["centroid_class"]
    binary = state["binary"]
    upd = h if cfg.update_with == "encoded" else queries

    sims = queries @ binary.T  # (B, C)
    pred_t = jnp.argmax(sims, axis=-1)  # (B,)
    pred_class = centroid_class[pred_t]
    mis = (pred_class != labels).astype(jnp.float32)  # (B,)

    neg = jnp.finfo(sims.dtype).min
    own = centroid_class[None, :] == labels[:, None]  # (B, C)
    true_t = jnp.argmax(jnp.where(own, sims, neg), axis=-1)  # (B,)

    coef = (cfg.lr * mis)[:, None] * upd  # (B, D)
    fp = state["fp"]
    fp = fp.at[true_t].add(coef)
    fp = fp.at[pred_t].add(-coef)
    return dict(state, fp=fp), mis.sum()


def refresh_am(fp: Array, binary: Array, cfg: MemhdConfig,
               ) -> Tuple[Array, Array]:
    """Step 4 (normalize + re-binarize) on raw AM buffers.

    The ONE implementation of the binary-AM refresh; the epoch finalize,
    the in-scan ``refresh_every`` cond, and the sharded engine all call
    this so their step-4 semantics cannot diverge.
    """
    del binary
    fp = _normalize_fp(fp, cfg.normalize)
    return fp, am_lib.binarize_am(fp, cfg.threshold)


@partial(jax.jit, static_argnames=("cfg",))
def qail_finalize_epoch(state: AmState, cfg: MemhdConfig) -> AmState:
    """Step 4 (normalize + re-binarize) for the batched variant."""
    fp, binary = refresh_am(state["fp"], state["binary"], cfg)
    return dict(state, fp=fp, binary=binary)


# ---------------------------------------------------------------------------
# Device-resident scan engine
# ---------------------------------------------------------------------------

def prebatch(h: Array, q: Array, labels: Array, batch_size: int,
             ) -> Tuple[Array, Array, Array, Array]:
    """Reshape an epoch's data into device-resident minibatches.

    Pads n up to a multiple of ``batch_size`` (padded samples carry
    label -1 and mask 0, so they can never fire an Eq.-(6) update) and
    returns ``(hb, qb, yb, mask)`` shaped ``(n_batches, batch_size, ...)``
    — the scan axis of ``qail_epoch_scan``. Do this ONCE per fit; the
    same batched arrays serve every epoch.
    """
    n = h.shape[0]
    nb = -(-n // batch_size)
    pad = nb * batch_size - n
    mask = jnp.concatenate([jnp.ones((n,), jnp.float32),
                            jnp.zeros((pad,), jnp.float32)])
    hb = jnp.pad(h, ((0, pad), (0, 0)))
    qb = jnp.pad(q, ((0, pad), (0, 0)))
    yb = jnp.pad(labels.astype(jnp.int32), (0, pad), constant_values=-1)
    d = h.shape[1]
    return (hb.reshape(nb, batch_size, d), qb.reshape(nb, batch_size, d),
            yb.reshape(nb, batch_size), mask.reshape(nb, batch_size))


def qail_epoch_scan(state: AmState, cfg: MemhdConfig,
                    hb: Array, qb: Array, yb: Array, mask: Array,
                    **opts) -> Tuple[AmState, Array]:
    """One QAIL epoch as a single compiled ``lax.scan`` over minibatches.

    The jitted ``_epoch_scan`` (arguments below), with the AM state
    donated where the backend honours it.

    The whole epoch — sims MVM, Eq.-(4)/(5) target selection, Eq.-(6)
    scatter, and every mid-epoch binary refresh — runs device-resident in
    one dispatch. The AM buffers are donated on accelerator backends, so
    epoch N+1 trains in-place over epoch N's memory — this call CONSUMES
    ``state`` there (the ``state = qail_epoch_scan(state, ...)`` chain is
    the intended use; callers that must keep the old state alive should
    copy it first, or go through ``qail_epoch_batched`` which does).

    Args:
      state: AM state dict (fp, binary, centroid_class).
      cfg: MEMHD config (static).
      hb / qb / yb / mask: ``prebatch`` outputs, shape (n_batches, bs, ...).
      refresh_every: run step 4 (normalize + re-binarize) inside the scan
        every this-many batches. If the last batch refreshed, the epoch
        ends there — no redundant trailing finalize (the pre-refactor
        host loop double-finalized when n_batches % refresh_every == 0).
      use_kernel: route the fused inner step through the Pallas
        ``qail_update`` kernel (TPU; interpret elsewhere) instead of the
        pure-jnp scatter path. Both are oracle-checked against each other
        in tests/test_qail_engine.py.
      sim: optional ``ImcSimConfig`` (static) — the noise-aware QAIL
        hook. When it carries conductance noise or stuck-at faults, each
        batch's sims MVM (and Eq.-4/5 target selection) is evaluated
        against a device-perturbed view of the binary AM
        (``imcsim.device.perturb_binary``), so centroids learn margins
        that survive analog readout. The Eq.-(6) update still lands on
        the clean float shadow AM.
      noise_key: PRNG key for the perturbations; required when ``sim``
        injects noise/faults.
      noise_mode: "fixed" — every batch sees the SAME perturbation
        (keyed by ``noise_key`` alone): chip-in-the-loop training
        against one deterministic device instance, QAIL's
        train-on-the-deployed-representation principle taken down to
        the device level. "fresh" — a new draw per batch
        (fold_in(noise_key, batch)): trains for expected accuracy over
        the device distribution.
      cell_bits: optional (static) — the quantization-aware hook for
        the ``target="multibit"`` deployment. When set (2..8), each
        batch's sims MVM sees the symmetric ``cell_bits``-bit
        quantization of the LIVE float shadow (``am.quantize_am``
        codes; argmax is scale-invariant) instead of the binary AM, so
        Eq.-(4)/(5) targets are selected against the representation the
        multibit backend will actually serve. The Eq.-(6) update still
        lands on the clean float shadow, exactly as the 1-bit paper
        loop (and the noise-aware hook) does. Composes with ``sim``
        conductance noise (drawn per level step, on the code view);
        stuck-at faults are 1-bit-cell semantics and are rejected.

    Returns:
      (state, n_miss) — n_miss is a DEVICE scalar; pulling it is the
      caller's one permitted host sync per epoch.
    """
    return _epoch_scan_jit(_donate())(state, cfg, hb, qb, yb, mask, **opts)


@cache
def _epoch_scan_jit(donate: Tuple[int, ...]):
    return jax.jit(_epoch_scan,
                   static_argnames=("cfg", "refresh_every", "use_kernel",
                                    "sim", "noise_mode", "cell_bits"),
                   donate_argnums=donate)


def _epoch_scan(state: AmState, cfg: MemhdConfig,
                hb: Array, qb: Array, yb: Array, mask: Array,
                *, refresh_every: int = 1,
                use_kernel: bool = False,
                sim=None, noise_key: Array = None,
                noise_mode: str = "fixed",
                cell_bits: Optional[int] = None,
                ) -> Tuple[AmState, Array]:
    global _scan_trace_count
    _scan_trace_count += 1

    centroid_class = state["centroid_class"]
    nb = hb.shape[0]

    noisy = sim is not None and (sim.noise_sigma > 0.0
                                 or sim.fault_p0 > 0.0
                                 or sim.fault_p1 > 0.0)
    if sim is not None and not noisy:
        # The hook injects storage-path effects (conductance noise,
        # stuck-at faults); a sim whose only non-ideality is the ADC or
        # readout drift would silently train plain QAIL — refuse rather
        # than report a bogus "noise-aware" run.
        raise ValueError(
            "sim carries no conductance noise or stuck-at faults; the "
            "noise-aware hook would be a no-op (ADC/drift live in the "
            "readout path, not the training MVM) — pass sim=None or a "
            "sim with noise_sigma/fault_p0/fault_p1 > 0")
    if noisy and noise_key is None:
        raise ValueError("sim injects device noise: pass noise_key")
    if noise_mode not in ("fixed", "fresh"):
        raise ValueError(f"bad noise_mode: {noise_mode!r}")
    if cell_bits is not None:
        if not 2 <= cell_bits <= 8:
            raise ValueError(f"cell_bits={cell_bits} outside [2, 8]")
        if noisy and (sim.fault_p0 > 0.0 or sim.fault_p1 > 0.0):
            raise ValueError(
                "stuck-at faults are 1-bit storage semantics; the "
                "multibit QAT hook composes with conductance noise only")

    def _refresh(args):
        return refresh_am(args[0], args[1], cfg)

    def body(carry, xs):
        fp, binary = carry
        b_idx, hx, qx, yx, mx = xs
        upd = hx if cfg.update_with == "encoded" else qx
        if cell_bits is not None:
            # Quantization-aware view: the live float shadow's
            # cell_bits-bit codes (re-quantized per batch — the multibit
            # analogue of refresh_every=1 for the binary AM).
            codes, _ = am_lib.quantize_am(fp, cell_bits)
            binary_mvm = codes.astype(jnp.float32)
        else:
            binary_mvm = binary
        if noisy:
            from repro.imcsim import device as device_lib
            bkey = (noise_key if noise_mode == "fixed"
                    else jax.random.fold_in(noise_key, b_idx))
            if cell_bits is not None:
                # Code-domain conductance noise: sigma per level step
                # (faults were rejected above).
                binary_mvm = device_lib.conductance_noise(
                    bkey, binary_mvm, sim.noise_sigma)
            else:
                binary_mvm = device_lib.perturb_binary(bkey, binary_mvm,
                                                       sim)
        if use_kernel:
            from repro.kernels import ops
            delta, miss = ops.qail_update(
                qx, upd, binary_mvm.T, centroid_class, yx, mx, lr=cfg.lr)
            fp = fp + delta
        else:
            sims = qx @ binary_mvm.T  # (bs, C)
            pred_t = jnp.argmax(sims, axis=-1)
            mis = (centroid_class[pred_t] != yx).astype(jnp.float32) * mx
            neg = jnp.finfo(sims.dtype).min
            own = centroid_class[None, :] == yx[:, None]
            true_t = jnp.argmax(jnp.where(own, sims, neg), axis=-1)
            coef = (cfg.lr * mis)[:, None] * upd
            fp = fp.at[true_t].add(coef)
            fp = fp.at[pred_t].add(-coef)
            miss = mis.sum()
        fp, binary = jax.lax.cond(
            (b_idx + 1) % refresh_every == 0, _refresh, lambda a: a,
            (fp, binary))
        return (fp, binary), miss

    (fp, binary), misses = jax.lax.scan(
        body, (state["fp"], state["binary"]),
        (jnp.arange(nb), hb, qb, yb, mask))
    state = dict(state, fp=fp, binary=binary)
    if nb % refresh_every != 0:  # last batch didn't refresh inside scan
        state = qail_finalize_epoch(state, cfg)
    return state, misses.sum()


def qail_epoch_batched(state: AmState, cfg: MemhdConfig,
                       h: Array, queries: Array, labels: Array,
                       *, refresh_every: int = 1,
                       use_kernel: bool = False,
                       ) -> Tuple[AmState, Array]:
    """One scan-compiled epoch over unbatched (n, D) arrays.

    Convenience wrapper: ``prebatch`` + ``qail_epoch_scan``. Callers that
    run many epochs (fit, the train driver) should prebatch once and call
    ``qail_epoch_scan`` directly. Unlike the raw engine, this wrapper
    does NOT consume ``state`` — on donating backends it hands the scan a
    copy, so ad-hoc callers (tests, notebooks) can keep reusing theirs.

    Returns:
      (state, miss_rate) — miss rate is a device scalar (pre-update AMs).
    """
    n = h.shape[0]
    hb, qb, yb, mask = prebatch(h, queries, labels, cfg.batch_size)
    if _donate():
        state = jax.tree.map(jnp.copy, state)
    state, n_miss = qail_epoch_scan(state, cfg, hb, qb, yb, mask,
                                    refresh_every=refresh_every,
                                    use_kernel=use_kernel)
    return state, n_miss / n


def fold_feedback(state: AmState, cfg: MemhdConfig,
                  h: Array, queries: Array, labels: Array,
                  *, epochs: int = 1, refresh_every: int = 1,
                  use_kernel: bool = False,
                  ) -> Tuple[AmState, float]:
    """Fold a labeled feedback buffer into the AM — the online-learning
    primitive behind ``repro.serve.StreamingUpdater``.

    A lean ``fit(init_method="keep")``: no clustering init, no eval, no
    checkpointing — just ``prebatch`` once and run ``epochs``
    device-resident ``qail_epoch_scan`` passes over the buffer. Every
    label must own at least one centroid (grow the AM first via
    ``MemhdModel.grow_classes`` when feedback carries never-seen
    classes — Eq.-(5)'s ownership-masked argmax silently corrupts the
    update otherwise). Non-consuming: on donating backends the scan gets
    a copy, so the caller's ``state`` — typically the live serving
    model's — survives.

    Returns (new_state, miss_rate) with miss_rate from the LAST epoch
    (one host sync total — earlier epochs' miss scalars are never
    pulled).
    """
    if epochs < 1:
        raise ValueError(f"epochs must be >= 1, got {epochs}")
    n = h.shape[0]
    hb, qb, yb, mask = prebatch(h, queries, labels, cfg.batch_size)
    if _donate():
        state = jax.tree.map(jnp.copy, state)
    n_miss = jnp.zeros(())
    for _ in range(epochs):
        state, n_miss = qail_epoch_scan(state, cfg, hb, qb, yb, mask,
                                        refresh_every=refresh_every,
                                        use_kernel=use_kernel)
    return state, float(n_miss) / n


def qail_epoch_hostloop(state: AmState, cfg: MemhdConfig,
                        h: Array, queries: Array, labels: Array,
                        *, refresh_every: int = 1) -> Tuple[AmState, float]:
    """Pre-refactor host-side epoch loop (one dispatch + sync PER BATCH).

    Kept as the measured baseline of benchmarks/train_throughput.py and
    as a semantics oracle for ``qail_epoch_scan`` (which it must match —
    the former double finalize at epoch end when
    ``n_batches % refresh_every == 0`` is fixed in both).
    """
    n = h.shape[0]
    bs = cfg.batch_size
    n_batches = -(-n // bs)
    total_miss = 0.0
    for b in range(n_batches):
        sl = slice(b * bs, min((b + 1) * bs, n))
        state, miss = qail_batch_update(
            state, cfg, h[sl], queries[sl], labels[sl])
        total_miss += float(miss)  # <- the per-batch host sync
        if (b + 1) % refresh_every == 0:
            state = qail_finalize_epoch(state, cfg)
    if n_batches % refresh_every != 0:
        state = qail_finalize_epoch(state, cfg)
    return state, total_miss / n


def evaluate(state: AmState, queries: Array, labels: Array,
             batch: int = 4096) -> float:
    """Classification accuracy of the binary AM on (queries, labels)."""
    from repro.core import evaluate as eval_lib
    return eval_lib.am_accuracy(state, queries, labels, batch=batch)
