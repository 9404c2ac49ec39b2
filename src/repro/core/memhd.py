"""MEMHD end-to-end model: encode -> cluster-init -> QAIL -> deploy.

This is the public, paper-faithful pipeline (Fig. 2):

    model  = MemhdModel.create(key, enc_cfg, am_cfg)
    model, hist = model.fit(key, feats, labels)       # (a)-(c) of Fig. 2
    acc    = model.score(test_feats, test_labels)     # (d) in-memory inference

``MemhdModel`` is an immutable pytree-of-arrays + static configs, so it
jits, shards, and checkpoints like any other model in the framework.

Training at scale
-----------------
``fit`` encodes the training set ONCE and runs every epoch as a single
compiled ``lax.scan`` (``qail.qail_epoch_scan``) — one dispatch and one
host sync per epoch. Pass ``ckpt=CheckpointManager(...)`` and the fit
checkpoints a ``MemhdTrainState`` every ``ckpt_every`` epochs and
auto-resumes bit-exactly from the newest valid one; the fault-tolerant
driver (``repro.launch.train --arch memhd``) builds on exactly this
path. ``fit_sharded`` runs the same scan epochs data-parallel over a
device mesh (per-shard Eq.-(6) deltas, one bf16 all-reduce per batch).
"""
from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import am as am_lib
from repro.core import encoding, evaluate as eval_lib, init as init_lib, qail
from repro.core.imc import ImcArrayConfig, memhd_pipeline
from repro.core.types import EncoderConfig, MemhdConfig
from repro.obs import span

Array = jax.Array
log = logging.getLogger(__name__)


def _imc_cost(enc_cfg: EncoderConfig, am_cfg: MemhdConfig,
              arr: ImcArrayConfig | None):
    arr = arr or ImcArrayConfig()
    return memhd_pipeline(enc_cfg.features, am_cfg.dim, am_cfg.columns,
                          arr)


@partial(jax.jit, static_argnames=("enc_cfg",))
def _predict_feats(enc_params, enc_cfg: EncoderConfig, binary: Array,
                   centroid_class: Array, feats: Array) -> Array:
    """encode_query + associative search, one cached executable."""
    q = encoding.encode_query(enc_params, enc_cfg, feats)
    return am_lib.predict(binary, centroid_class, q)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MemhdTrainState:
    """Checkpointable training state: AM buffers + epoch counter.

    A plain pytree (both fields are array leaves), so it flows through
    ``checkpoint.CheckpointManager`` unchanged — the driver's atomic
    save / verified restore / keep-k machinery applies as-is.
    """

    am_state: Dict[str, Array]
    epoch: Array  # () int32

    def tree_flatten(self):
        return (self.am_state, self.epoch), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        am_state, epoch = children
        return cls(am_state, epoch)

    @classmethod
    def create(cls, am_state: Dict[str, Array],
               epoch: int = 0) -> "MemhdTrainState":
        return cls(am_state, jnp.asarray(epoch, jnp.int32))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MemhdModel:
    """Immutable MEMHD model (encoder params + AM state + configs)."""

    enc_params: Dict[str, Array]
    am_state: Dict[str, Array]
    enc_cfg: EncoderConfig
    am_cfg: MemhdConfig

    # -- pytree protocol -----------------------------------------------------
    def tree_flatten(self):
        return (self.enc_params, self.am_state), (self.enc_cfg, self.am_cfg)

    @classmethod
    def tree_unflatten(cls, aux, children):
        enc_params, am_state = children
        enc_cfg, am_cfg = aux
        return cls(enc_params, am_state, enc_cfg, am_cfg)

    # -- construction ----------------------------------------------------------
    @classmethod
    def create(cls, key: Array, enc_cfg: EncoderConfig, am_cfg: MemhdConfig,
               ) -> "MemhdModel":
        if enc_cfg.dim != am_cfg.dim:
            raise ValueError(
                f"encoder D={enc_cfg.dim} != AM D={am_cfg.dim}")
        enc_params = encoding.init_encoder(key, enc_cfg)
        # AM starts empty; fit() builds it via clustering init.
        zeros = jnp.zeros((am_cfg.columns, am_cfg.dim), jnp.float32)
        owners = jnp.zeros((am_cfg.columns,), jnp.int32)
        return cls(enc_params, am_lib.make_am_state(zeros, owners,
                                                    am_cfg.threshold),
                   enc_cfg, am_cfg)

    # -- pipeline stages -------------------------------------------------------
    def encode(self, feats: Array) -> Array:
        return encoding.encode(self.enc_params, self.enc_cfg, feats)

    def encode_query(self, feats: Array) -> Array:
        return encoding.encode_query(self.enc_params, self.enc_cfg, feats)

    def initialize_am(self, key: Array, feats: Array, labels: Array,
                      *, method: str = "clustering",
                      h: Optional[Array] = None,
                      q: Optional[Array] = None,
                      ) -> Tuple["MemhdModel", List[dict]]:
        """Clustering-based (or random-sampling baseline) AM init (§III-A).

        Pass pre-encoded ``h`` / ``q`` to reuse an existing encode of
        ``feats`` (``fit`` does — the training set is encoded exactly
        once per fit, not once for init and again for the epochs).
        """
        if h is None:
            h = self.encode(feats)
        if q is None:
            q = encoding.binarize_query(h)
        if method == "clustering":
            fp, owners, history = init_lib.clustering_init(
                key, self.am_cfg, h, labels, queries=q)
        elif method == "random":
            fp, owners = init_lib.random_sampling_init(
                key, self.am_cfg, h, labels)
            history = []
        else:
            raise ValueError(f"unknown init method {method!r}")
        state = am_lib.make_am_state(fp, owners, self.am_cfg.threshold)
        return dataclasses.replace(self, am_state=state), history

    def fit(self, key: Array, feats: Array, labels: Array,
            *, init_method: str = "clustering",
            epochs: Optional[int] = None,
            mode: str = "batched",
            refresh_every: int = 1,
            eval_feats: Optional[Array] = None,
            eval_labels: Optional[Array] = None,
            ckpt=None, ckpt_every: int = 1,
            use_kernel: bool = False,
            noise_sim=None, noise_mode: str = "fixed",
            cell_bits: Optional[int] = None,
            ) -> Tuple["MemhdModel", Dict]:
        """Full training pipeline: init + scan-compiled QAIL epochs.

        The training set is encoded ONCE; both the clustering init and
        every epoch reuse the same device-resident ``h``/``q``/prebatched
        buffers. Each ``batched``-mode epoch is a single
        ``qail_epoch_scan`` dispatch — one host sync per epoch (the
        ``float(miss)`` for the history record).

        Host spans (``repro.obs.span``): ``fit`` covers the call;
        ``fit.encode`` the encode, binarize and batching dispatch; in
        each epoch ``fit.epoch`` the epoch's dispatch and ``fit.sync``
        the miss count's host sync (both with ``epoch=``).

        Args:
          refresh_every: binary-AM refresh cadence inside the epoch scan
            (1 = per batch; larger trades fidelity for fewer
            binarization passes).
          ckpt: optional ``checkpoint.CheckpointManager``. When given,
            fit auto-resumes from the newest valid ``MemhdTrainState``
            (bit-exact continuation) and checkpoints every ``ckpt_every``
            epochs plus at the end.
          use_kernel: route the epoch's inner step through the Pallas
            ``qail_update`` kernel.
          init_method: "clustering" (paper §III-A), "random", or "keep"
            — keep the CURRENT AM state and skip (re-)initialization;
            the fine-tuning mode ``imcsim.noise_aware`` builds on.
          noise_sim: optional ``ImcSimConfig`` — noise-aware QAIL: the
            training-time sims MVM sees a device-perturbed view of the
            binary AM (batched mode only; see ``qail.qail_epoch_scan``).
          noise_mode: "fixed" (default) trains against the ONE device
            instance ``deploy(target="imc", sim=noise_sim)`` will burn
            in (chip-in-the-loop); "fresh" redraws the perturbation per
            batch (robustness to the device distribution).
          cell_bits: optional — multi-bit quantization-aware QAIL: the
            training-time sims MVM sees the ``cell_bits``-bit quantized
            view of the live float shadow, the representation
            ``deploy(target="multibit", cell_bits=...)`` serves
            (batched mode only; composes with a conductance-noise
            ``noise_sim``; see ``qail.qail_epoch_scan``).

        Returns (model, history) where history holds per-epoch train miss
        rates and (optional) eval accuracies — consumed by the Fig.-5/6
        benchmarks.
        """
        with span("fit"):
            epochs = self.am_cfg.epochs if epochs is None else epochs
            if noise_sim is not None and mode != "batched":
                raise ValueError("noise_sim needs the batched scan engine")
            if cell_bits is not None and mode != "batched":
                raise ValueError("cell_bits needs the batched scan engine")

            # Encode once; init and every epoch share these buffers.
            with span("fit.encode"):
                h = self.encode(feats)
                q = encoding.binarize_query(h)
                if mode == "batched":
                    n = h.shape[0]
                    hb, qb, yb, mask = qail.prebatch(h, q, labels,
                                                     self.am_cfg.batch_size)

            start_epoch = 0
            init_hist: List[dict] = []
            curve: List[dict] = []
            state = None
            resumed = False
            if ckpt is not None:
                template = MemhdTrainState.create(self.am_state)
                step, tree, extra = ckpt.restore(template)
                if step is not None:
                    state = jax.tree.map(jnp.asarray, tree.am_state)
                    start_epoch = step
                    curve = list(extra.get("curve", []))
                    init_hist = list(extra.get("init", []))
                    resumed = True
                    log.info("fit resumed from epoch %d", start_epoch)

            if state is None:
                if init_method == "keep":
                    model, init_hist = self, []
                    state = self.am_state
                else:
                    model, init_hist = self.initialize_am(
                        key, feats, labels, method=init_method, h=h, q=q)
                    state = model.am_state
            else:
                model = dataclasses.replace(self, am_state=state)

            eval_q = (model.encode_query(eval_feats)
                      if eval_feats is not None else None)

            def _save(ep, st):
                if ckpt is not None:
                    ckpt.save(ep, MemhdTrainState.create(st, ep),
                              extra={"curve": curve, "init": init_hist})

            if start_epoch == 0 and not resumed:
                if eval_q is not None:
                    acc0 = qail.evaluate(state, eval_q, eval_labels)
                    curve.append({"epoch": 0, "eval_acc": acc0})
                _save(0, state)

            noise_base = None
            if noise_sim is not None:
                from repro.imcsim import device as device_lib
                noise_base = (device_lib.device_instance_key(noise_sim)
                              if noise_mode == "fixed"
                              else jax.random.key(noise_sim.seed))
            for ep in range(start_epoch + 1, epochs + 1):
                if mode == "sequential":
                    with span("fit.epoch", epoch=ep):
                        state = qail.qail_epoch_sequential(
                            state, self.am_cfg, h, q, labels)
                    miss = float("nan")
                else:
                    nkey = None
                    if noise_base is not None:
                        nkey = (noise_base if noise_mode == "fixed"
                                else jax.random.fold_in(noise_base, ep))
                    with span("fit.epoch", epoch=ep):
                        state, n_miss = qail.qail_epoch_scan(
                            state, self.am_cfg, hb, qb, yb, mask,
                            refresh_every=refresh_every,
                            use_kernel=use_kernel, sim=noise_sim,
                            noise_key=nkey, noise_mode=noise_mode,
                            cell_bits=cell_bits)
                    with span("fit.sync", epoch=ep):
                        # The ONE host sync this epoch.
                        miss = float(n_miss) / n
                rec = {"epoch": ep, "train_miss": miss}
                if eval_q is not None:
                    rec["eval_acc"] = qail.evaluate(state, eval_q, eval_labels)
                curve.append(rec)
                if ep % ckpt_every == 0 or ep == epochs:
                    _save(ep, state)
            model = dataclasses.replace(model, am_state=state)
            return model, {"init": init_hist, "curve": curve}

    def fit_sharded(self, key: Array, feats: Array, labels: Array,
                    *, mesh=None, epochs: Optional[int] = None,
                    init_method: str = "clustering",
                    refresh_every: int = 1,
                    ) -> Tuple["MemhdModel", Dict]:
        """Data-parallel fit: scan-compiled epochs under ``shard_map``.

        The batch axis of every prebatched minibatch shards over the
        mesh; each shard computes its Eq.-(6) delta (``qail_batch_delta``)
        and the shards sync with ONE bf16 all-reduce per batch (the
        wire-dtype machinery of §Perf Q2). The AM is replicated — it is
        the model, and it is tiny by construction.
        """
        from repro.core import distributed

        if mesh is None:
            mesh = jax.make_mesh((jax.device_count(),), ("data",))
        epochs = self.am_cfg.epochs if epochs is None else epochs

        h = self.encode(feats)
        q = encoding.binarize_query(h)
        model, init_hist = self.initialize_am(
            key, feats, labels, method=init_method, h=h, q=q)

        n = h.shape[0]
        n_shards = int(mesh.devices.size)
        bs = -(-self.am_cfg.batch_size // n_shards) * n_shards
        hb, qb, yb, mask = qail.prebatch(h, q, labels, bs)

        state, curve = distributed.fit_sharded_epochs(
            mesh, model.am_state, self.am_cfg, hb, qb, yb, mask,
            epochs=epochs, refresh_every=refresh_every, n_samples=n)
        model = dataclasses.replace(model, am_state=state)
        return model, {"init": init_hist, "curve": curve}

    # -- class-incremental growth ------------------------------------------------
    def grow_classes(self, feats: Array, labels: Array,
                     *, centroids_per_class: int = 1,
                     h: Optional[Array] = None,
                     ) -> "MemhdModel":
        """Append never-seen classes to the AM: (C, D) -> (C + k·n, D).

        The extended-learning move (XL-HD): classes beyond the current
        ``am_cfg.classes`` get fresh centroids — the per-class mean of
        their encoded samples (chunk-split when ``centroids_per_class``
        > 1), rescaled to the mean norm of the existing float centroids
        so Eq.-(6) nudges and the global binarization threshold stay
        proportionate — WITHOUT touching the existing centroids or
        retraining. The returned model is a normal ``MemhdModel`` at the
        grown geometry; follow with ``fit(init_method="keep")`` (or
        ``qail.fold_feedback``) to polish the new rows against the old.

        Growth MUST happen before folding feedback that carries the new
        labels: QAIL's Eq.-(5) target selection masks on centroid
        ownership, and a label owning no centroid silently corrupts the
        update (the masked argmax degenerates to centroid 0).

        Args:
          feats: (n, f) raw feature rows; only rows labeled beyond the
            current class count seed new centroids.
          labels: (n,) int labels. New classes must be contiguous from
            ``am_cfg.classes`` (class ids are dense by construction
            everywhere else).
          centroids_per_class: centroids allocated per appended class.
          h: optional pre-encoded ``encode(feats)`` to reuse (the
            encoder is untouched by growth, so any encode stays valid).

        Returns:
          The grown model (new ``am_state`` + ``am_cfg``; encoder
          shared). Raises if no label exceeds the current classes.
        """
        import numpy as np
        old_k = self.am_cfg.classes
        yn = np.asarray(labels, np.int64)
        new_classes = sorted(int(c) for c in np.unique(yn) if c >= old_k)
        if not new_classes:
            raise ValueError(
                f"no labels beyond the current {old_k} classes")
        if new_classes != list(range(old_k, old_k + len(new_classes))):
            raise ValueError(
                f"appended classes must be contiguous from {old_k}, "
                f"got {new_classes}")
        if centroids_per_class < 1:
            raise ValueError("centroids_per_class must be >= 1")
        if h is None:
            h = self.encode(feats)
        hn = np.asarray(h, np.float32)

        fp = self.am_state["fp"]
        owners = self.am_state["centroid_class"]
        scale = float(jnp.mean(jnp.linalg.norm(fp, axis=-1)))
        rows, row_owners = [], []
        for c in new_classes:
            members = hn[yn == c]
            if members.shape[0] == 0:
                raise ValueError(f"class {c} has no samples to seed from")
            for part in np.array_split(members, centroids_per_class):
                m = (part if part.shape[0] else members).mean(axis=0)
                if scale > 0:
                    m = m * (scale / max(float(np.linalg.norm(m)), 1e-8))
                rows.append(m)
                row_owners.append(c)

        fp_new = jnp.concatenate(
            [fp, jnp.asarray(np.stack(rows), jnp.float32)])
        owners_new = jnp.concatenate(
            [owners, jnp.asarray(row_owners, jnp.int32)])
        cfg = dataclasses.replace(
            self.am_cfg,
            columns=self.am_cfg.columns + len(rows),
            classes=old_k + len(new_classes))
        state = am_lib.make_am_state(fp_new, owners_new, cfg.threshold)
        return MemhdModel(self.enc_params, state, self.enc_cfg, cfg)

    # -- inference ---------------------------------------------------------------
    def predict(self, feats: Array) -> Array:
        return _predict_feats(self.enc_params, self.enc_cfg,
                              self.am_state["binary"],
                              self.am_state["centroid_class"], feats)

    def score(self, feats: Array, labels: Array, batch: int = 4096) -> float:
        return eval_lib.batched_accuracy(self.predict, feats, labels, batch)

    # -- deployment --------------------------------------------------------------
    def deploy(self, *, target: Optional[str] = None,
               packed: Optional[bool] = None, mode: Optional[str] = None,
               sim=None, **opts):
        """Freeze the trained model into its serving artifact.

        Canonical form: ``deploy(target=t, **backend_opts)`` with ``t``
        a registered deployment backend (``repro.deploy.registry``):

        * ``"packed"`` (default) — the (Dp, C) uint8 1-bit residence the
          paper's Table I counts, served by the fused XOR+popcount
          kernel (``mode="popcount" | "unpack"``).
        * ``"unpacked"`` — the ±1 float AM and the float ``am_search``
          kernel; the bit-exact parity baseline.
        * ``"imc"`` — a *simulated analog device* (``repro.imcsim``):
          the binary AM is burned in with the stuck-at faults /
          conductance variation of ``sim`` (an ``ImcSimConfig``; seeded,
          so the same config always yields the same device) and queries
          go through the tiled analog-partial-sum + ADC kernel. Ideal
          ``sim`` == bit-exact with the digital artifacts.

        Every artifact implements the same ``DeployedArtifact``
        protocol, so serving code is backend-agnostic; wrap any of them
        in ``repro.deploy.ShardedArtifact`` for multi-device serving.

        Legacy forms keep working: ``deploy(packed=False)`` and
        ``target="digital"`` map onto the registry targets.
        """
        from repro import deploy as deploy_lib
        if target in (None, "digital"):
            if sim is not None:
                raise ValueError(
                    "sim= is only meaningful with target='imc'")
            target = "unpacked" if packed is False else "packed"
        elif packed is not None:
            raise ValueError(
                "packed= is the legacy digital switch; use "
                "target='packed' / target='unpacked' instead")
        if mode is not None:
            opts["mode"] = mode
        if sim is not None:
            opts["sim"] = sim
        return deploy_lib.deploy(self, target, **opts)

    # -- deployment accounting -----------------------------------------------------
    @property
    def memory_bits(self) -> int:
        """EM + AM bits, per Table I (f*D + C*D binary)."""
        return self.enc_cfg.memory_bits + self.am_cfg.am_memory_bits

    @property
    def memory_kb(self) -> float:
        return self.memory_bits / 8 / 1024

    def imc_cost(self, arr: ImcArrayConfig | None = None):
        return _imc_cost(self.enc_cfg, self.am_cfg, arr)


# Re-export shim: the digital serving artifact moved to the unified
# deployment subsystem (repro.deploy.digital); existing imports of
# ``repro.core.memhd.DeployedMemhd`` / ``repro.core.DeployedMemhd``
# keep working.
from repro.deploy.digital import DeployedMemhd  # noqa: E402,F401
