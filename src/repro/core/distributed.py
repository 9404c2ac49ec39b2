"""Distributed MEMHD training: data-parallel QAIL under pjit.

The paper trains on a workstation; here the same algorithm is expressed
as a pod-scale program — the point of integrating MEMHD as a first-class
feature of the framework rather than a side script:

  * encoding (the f x D binary MVM) shards over the batch axes;
  * the AM (C x D, <= a few MB binary) is replicated — it is the *model*,
    and it is tiny by construction (that is the paper's whole thesis);
  * Eq.-(6) scatter-updates from each batch shard are partial sums into
    the replicated float AM; GSPMD inserts the cross-shard psum;
  * step 4 (normalize + re-binarize) is replicated compute.

``dryrun_epoch`` lowers + compiles one full QAIL epoch over an
MNIST-sized dataset on the production mesh and extracts the same
roofline terms as the LM cells — the "most representative of the paper's
technique" row of §Perf.

``make_scan_epoch_sharded`` / ``fit_sharded_epochs`` are the
data-parallel mirror of the device-resident training engine
(``qail.qail_epoch_scan``): the whole epoch is one jitted shard_map'd
``lax.scan`` over prebatched minibatches — per-shard Eq.-(6) deltas,
one bf16 psum per batch, one host sync per epoch. This is what
``MemhdModel.fit_sharded`` runs.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import qail
from repro.core.types import EncoderConfig, MemhdConfig


Array = jax.Array


def _batch_axes(mesh) -> tuple:
    """MEMHD shards the batch over EVERY mesh axis.

    The model (binary AM + projection, a few MB) is replicated — that is
    the paper's thesis — so there is nothing for a tensor axis to do;
    leaving "model" out of the batch sharding replicates all compute 16x
    (measured: useful-FLOPs ratio 0.0625 == 1/16; §Perf iteration Q1).
    """
    return tuple(mesh.axis_names)


def make_epoch_fn(enc_cfg: EncoderConfig, am_cfg: MemhdConfig,
                  mesh=None):
    """(enc_params, am_state, feats, labels) -> (am_state, miss_rate).

    One full QAIL epoch: encode -> binary similarity -> Eq. 4/5 target
    selection -> Eq. 6 scatter updates -> normalize -> re-binarize.
    Batched semantics (one binary-AM snapshot per epoch) — the variant
    the paper's §III-C runs per pass over the training set.
    """

    def epoch(enc_params, am_state, feats, labels):
        """shard_map over the whole mesh: per-shard encode + Eq.-6 delta,
        ONE explicit bf16 psum for the AM sync (§Perf Q2 — GSPMD left to
        itself emitted two f32[C,D] all-reduces; the explicit psum pins
        the wire format and fuses the miss-count ride-along)."""
        if mesh is None:
            # Single-device path (tests without meshes).
            m = enc_params["projection"]
            h = jnp.einsum("bf,fd->bd", feats, m)
            q = jnp.where(h >= 0, 1.0, -1.0)
            delta, miss = qail.qail_batch_delta(am_state, am_cfg, h, q,
                                                labels)
            state = dict(am_state,
                         fp=am_state["fp"] + delta.astype(jnp.float32))
            state = qail.qail_finalize_epoch(state, am_cfg)
            return state, miss / feats.shape[0]

        all_axes = tuple(mesh.axis_names)

        def local(m, fp, binary, owners, feats_l, labels_l):
            # bf16 streaming + MXU-native bf16 MVM, f32 accumulation
            # (§Perf Q4): the projection is ±1 so bf16 operands are
            # exact; only the accumulate needs f32.
            h = jnp.einsum("bf,fd->bd", feats_l.astype(jnp.bfloat16),
                           m.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
            q = jnp.where(h >= 0, 1.0, -1.0)
            st = {"fp": fp, "binary": binary, "centroid_class": owners}
            delta, miss = qail.qail_batch_delta(st, am_cfg, h, q, labels_l)
            delta = jax.lax.psum(delta, all_axes)        # bf16 wire
            miss = jax.lax.psum(miss, all_axes)
            new_fp = fp + delta.astype(jnp.float32)
            return new_fp, miss

        from jax.sharding import PartitionSpec as P
        new_fp, miss = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(all_axes, None), P(all_axes)),
            out_specs=(P(), P()),
        )(enc_params["projection"], am_state["fp"], am_state["binary"],
          am_state["centroid_class"], feats, labels)
        state = dict(am_state, fp=new_fp)
        state = qail.qail_finalize_epoch(state, am_cfg)
        return state, miss / feats.shape[0]

    return epoch


def shardings_for(mesh, enc_cfg: EncoderConfig, am_cfg: MemhdConfig):
    ba = _batch_axes(mesh)
    repl = NamedSharding(mesh, P())
    return {
        "enc": {"projection": repl},
        "am": {"fp": repl, "binary": repl, "centroid_class": repl},
        "feats": NamedSharding(mesh, P(ba, None)),
        "labels": NamedSharding(mesh, P(ba)),
    }


def fit_distributed(mesh, model, feats: Array, labels: Array,
                    epochs: Optional[int] = None):
    """Run QAIL epochs under pjit on ``mesh``. Returns updated model."""
    import dataclasses

    am_cfg = model.am_cfg
    epochs = am_cfg.epochs if epochs is None else epochs
    sh = shardings_for(mesh, model.enc_cfg, am_cfg)
    epoch = make_epoch_fn(model.enc_cfg, am_cfg, mesh)
    with mesh:
        fitted = jax.jit(
            epoch,
            in_shardings=(sh["enc"], sh["am"], sh["feats"], sh["labels"]),
            out_shardings=(sh["am"], None),
        )
        feats = jax.device_put(feats, sh["feats"])
        labels = jax.device_put(labels, sh["labels"])
        state = jax.device_put(model.am_state, sh["am"])
        enc = jax.device_put(model.enc_params, sh["enc"])
        for _ in range(epochs):
            state, _miss = fitted(enc, state, feats, labels)
    return dataclasses.replace(model, am_state=state)


def make_scan_epoch_sharded(cfg: MemhdConfig, mesh, refresh_every: int = 1):
    """Build a jit-able data-parallel scan epoch over prebatched data.

    (am_state, hb, qb, yb, mask) -> (am_state, n_miss), where the
    prebatched arrays are ``qail.prebatch`` outputs with the per-batch
    axis sharded over every mesh axis. Inside ``shard_map`` each shard
    runs the SAME ``lax.scan`` the single-device engine runs
    (``qail.qail_epoch_scan`` semantics), computing its local Eq.-(6)
    delta with ``qail_batch_delta`` and syncing with ONE bf16 psum per
    batch; the refresh (step 4) is replicated compute, identical on all
    shards because it consumes the psum'd float AM.
    """
    all_axes = tuple(mesh.axis_names)

    def epoch(am_state, hb, qb, yb, mask):
        nb = hb.shape[0]

        def _refresh(args):
            return qail.refresh_am(args[0], args[1], cfg)

        def local(fp, binary, owners, hb_l, qb_l, yb_l, mb_l):
            def body(carry, xs):
                fp, binary = carry
                b_idx, hx, qx, yx, mx = xs
                st = {"fp": fp, "binary": binary, "centroid_class": owners}
                delta, miss = qail.qail_batch_delta(
                    st, cfg, hx, qx, yx, mask=mx)
                delta = jax.lax.psum(delta, all_axes)  # bf16 wire
                miss = jax.lax.psum(miss, all_axes)
                fp = fp + delta.astype(jnp.float32)
                fp, binary = jax.lax.cond(
                    (b_idx + 1) % refresh_every == 0, _refresh,
                    lambda a: a, (fp, binary))
                return (fp, binary), miss

            (fp, binary), misses = jax.lax.scan(
                body, (fp, binary),
                (jnp.arange(nb), hb_l, qb_l, yb_l, mb_l))
            return fp, binary, misses.sum()

        fp, binary, n_miss = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(), P(None, all_axes, None),
                      P(None, all_axes, None), P(None, all_axes),
                      P(None, all_axes)),
            out_specs=(P(), P(), P()),
        )(am_state["fp"], am_state["binary"], am_state["centroid_class"],
          hb, qb, yb, mask)
        state = dict(am_state, fp=fp, binary=binary)
        if nb % refresh_every != 0:
            state = qail.qail_finalize_epoch(state, cfg)
        return state, n_miss

    return epoch


def fit_sharded_epochs(mesh, am_state, cfg: MemhdConfig,
                       hb: Array, qb: Array, yb: Array, mask: Array,
                       *, epochs: int, refresh_every: int = 1,
                       n_samples: Optional[int] = None):
    """Run ``epochs`` data-parallel scan epochs; one host sync per epoch.

    Returns (am_state, curve). The prebatched arrays are device_put with
    the per-batch axis sharded over the whole mesh; the AM is replicated.
    """
    n = n_samples if n_samples is not None else int(mask.sum())
    epoch = make_scan_epoch_sharded(cfg, mesh, refresh_every)
    repl = NamedSharding(mesh, P())
    ba = tuple(mesh.axis_names)
    sh_b2 = NamedSharding(mesh, P(None, ba))
    sh_b3 = NamedSharding(mesh, P(None, ba, None))
    am_sh = {"fp": repl, "binary": repl, "centroid_class": repl}
    with mesh:
        fitted = jax.jit(epoch,
                         in_shardings=(am_sh, sh_b3, sh_b3, sh_b2, sh_b2),
                         out_shardings=(am_sh, None))
        hb = jax.device_put(hb, sh_b3)
        qb = jax.device_put(qb, sh_b3)
        yb = jax.device_put(yb, sh_b2)
        mask = jax.device_put(mask, sh_b2)
        state = jax.device_put(am_state, am_sh)
        curve = []
        for ep in range(1, epochs + 1):
            state, n_miss = fitted(state, hb, qb, yb, mask)
            curve.append({"epoch": ep,
                          "train_miss": float(n_miss) / n})  # 1 sync/epoch
    return state, curve


def make_inference_fn(enc_cfg: EncoderConfig, am_cfg: MemhdConfig):
    """Batched one-shot associative search: feats -> predicted classes.

    The paper's deployment workload (§III-D): projection-encode,
    binarize, similarity MVM against the binary AM, arg-max, ownership
    lookup. Pure feed-forward — shards trivially over every mesh axis
    with a replicated few-MB model.
    """

    def infer(enc_params, binary_am, centroid_class, feats):
        h = jnp.einsum("bf,fd->bd", feats.astype(jnp.bfloat16),
                       enc_params["projection"].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        q = jnp.where(h >= 0, 1.0, -1.0).astype(jnp.bfloat16)
        sims = jnp.einsum("bd,cd->bc", q,
                          binary_am.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        return centroid_class[jnp.argmax(sims, axis=-1)]

    return infer


def dryrun_inference(mesh, *, features: int = 784, dim: int = 1024,
                     columns: int = 1024, n_queries: int = 1_048_576,
                     ) -> Dict:
    """Roofline of the batched one-shot search on the production mesh."""
    from repro.distributed import hlo_cost
    from repro.distributed.roofline import roofline

    enc_cfg = EncoderConfig(kind="projection", features=features, dim=dim)
    am_cfg = MemhdConfig(dim=dim, columns=columns)
    infer = make_inference_fn(enc_cfg, am_cfg)
    ba = _batch_axes(mesh)
    repl = NamedSharding(mesh, P())
    with mesh:
        compiled = jax.jit(
            infer,
            in_shardings=({"projection": repl}, repl, repl,
                          NamedSharding(mesh, P(ba, None))),
            out_shardings=NamedSharding(mesh, P(ba)),
        ).lower(
            {"projection": jax.ShapeDtypeStruct((features, dim),
                                                jnp.bfloat16)},
            jax.ShapeDtypeStruct((columns, dim), jnp.bfloat16),
            jax.ShapeDtypeStruct((columns,), jnp.int32),
            jax.ShapeDtypeStruct((n_queries, features), jnp.bfloat16),
        ).compile()

    chips = mesh.devices.size
    totals = hlo_cost.analyze(compiled.as_text(), chips)
    ma = compiled.memory_analysis()
    model_flops = 2.0 * n_queries * (features * dim + dim * columns)
    rep = roofline(
        arch="memhd-search", shape=f"{dim}x{columns}",
        mesh_name="x".join(str(s) for s in mesh.devices.shape),
        chips=chips, flops_per_dev=totals.flops,
        bytes_per_dev=totals.hbm_bytes, wire_by_kind=totals.wire_by_kind,
        model_flops_global=model_flops,
        argument_bytes=float(ma.argument_size_in_bytes),
        temp_bytes=float(ma.temp_size_in_bytes),
        output_bytes=float(ma.output_size_in_bytes),
    )
    return {"roofline": rep.to_json(),
            "memory": {"argument_bytes": int(ma.argument_size_in_bytes),
                       "temp_bytes": int(ma.temp_size_in_bytes)}}


def dryrun_epoch(mesh, *, features: int = 784, dim: int = 1024,
                 columns: int = 1024, classes: int = 10,
                 n_samples: int = 61_440) -> Dict:
    """Lower + compile one distributed QAIL epoch; roofline terms.

    Defaults: MNIST-scale (60k samples padded to a 256/512-divisible
    count) at the paper's largest geometry (1024x1024).
    """
    from repro.distributed import hlo_cost
    from repro.distributed.roofline import roofline

    enc_cfg = EncoderConfig(kind="projection", features=features, dim=dim)
    am_cfg = MemhdConfig(dim=dim, columns=columns, classes=classes)
    sh = shardings_for(mesh, enc_cfg, am_cfg)
    epoch = make_epoch_fn(enc_cfg, am_cfg, mesh)

    enc_sds = {"projection": jax.ShapeDtypeStruct((features, dim),
                                                  jnp.float32)}
    am_sds = {
        "fp": jax.ShapeDtypeStruct((columns, dim), jnp.float32),
        "binary": jax.ShapeDtypeStruct((columns, dim), jnp.float32),
        "centroid_class": jax.ShapeDtypeStruct((columns,), jnp.int32),
    }
    feats_sds = jax.ShapeDtypeStruct((n_samples, features), jnp.float32)
    labels_sds = jax.ShapeDtypeStruct((n_samples,), jnp.int32)

    with mesh:
        compiled = jax.jit(
            epoch,
            in_shardings=(sh["enc"], sh["am"], sh["feats"], sh["labels"]),
            out_shardings=(sh["am"], None),
        ).lower(enc_sds, am_sds, feats_sds, labels_sds).compile()

    chips = mesh.devices.size
    totals = hlo_cost.analyze(compiled.as_text(), chips)
    ma = compiled.memory_analysis()
    # Useful FLOPs: encode MVM + similarity MVM (fwd only; QAIL has no
    # backprop — one of the paper's efficiency arguments).
    model_flops = 2.0 * n_samples * (features * dim + dim * columns)
    rep = roofline(
        arch="memhd-qail", shape=f"{dim}x{columns}", mesh_name="x".join(
            str(s) for s in mesh.devices.shape),
        chips=chips, flops_per_dev=totals.flops,
        bytes_per_dev=totals.hbm_bytes, wire_by_kind=totals.wire_by_kind,
        model_flops_global=model_flops,
        argument_bytes=float(ma.argument_size_in_bytes),
        temp_bytes=float(ma.temp_size_in_bytes),
        output_bytes=float(ma.output_size_in_bytes),
    )
    return {"roofline": rep.to_json(),
            "memory": {"argument_bytes": int(ma.argument_size_in_bytes),
                       "temp_bytes": int(ma.temp_size_in_bytes)}}
