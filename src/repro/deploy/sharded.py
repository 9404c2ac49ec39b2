"""Multi-device sharded serving on top of any deployment backend.

``ShardedArtifact`` wraps a ``DeployedArtifact`` (any registry backend —
the wrapper only uses the protocol surface) and serves its query path
under ``shard_map`` over a 1-D data-parallel mesh: the artifact is
replicated (the AM is the model, and it is tiny by construction — the
paper's whole thesis), the batch axis shards over the devices, and each
shard runs the backend's own kernels on its rows. Predictions are
row-local, so sharded serving is bit-exact with the single-device path.

Ragged batches ride the existing padded-evaluator contract: the batch is
zero-padded up to a device multiple (zero feature rows encode to the
valid all-ones query) and the tail predictions are dropped before the
caller sees them.

    dep = model.deploy(target="packed")
    sharded = ShardedArtifact(dep, devices=8)   # or mesh=...
    preds = sharded.predict(feats)              # == dep.predict(feats)

``launch/serve_memhd.py --devices N`` and ``benchmarks/serve_scaling``
build on exactly this wrapper.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.deploy.padding import pad_rows, round_up

Array = jax.Array

DATA_AXIS = "data"


def serving_mesh(devices: Optional[Sequence] = None,
                 n: Optional[int] = None) -> Mesh:
    """A 1-D data-parallel mesh over the first ``n`` local devices."""
    devs = list(jax.devices()) if devices is None else list(devices)
    if n is not None:
        if n < 1 or n > len(devs):
            raise ValueError(
                f"requested {n} devices, have {len(devs)} "
                f"({[d.platform for d in devs[:4]]}...)")
        devs = devs[:n]
    return Mesh(np.array(devs), (DATA_AXIS,))


class ShardedArtifact:
    """Data-parallel serving wrapper around any deployment artifact.

    Query methods (``predict`` / ``predict_features`` /
    ``predict_query``) run under ``shard_map``; everything else —
    ``backend``, ``serving_mode``, residence accounting, configs —
    delegates to the wrapped artifact, so the wrapper drops into any
    code programmed against the ``DeployedArtifact`` protocol (the
    serving driver, ``build_report``, the benchmarks).
    """

    def __init__(self, artifact, mesh: Optional[Mesh] = None,
                 devices: Optional[int] = None):
        if isinstance(artifact, ShardedArtifact):
            raise TypeError("artifact is already sharded")
        self.artifact = artifact
        self.mesh = mesh if mesh is not None else serving_mesh(n=devices)
        if len(self.mesh.axis_names) != 1:
            raise ValueError("serving mesh must be 1-D (data-parallel)")
        self.n_devices = int(self.mesh.devices.size)
        self._fns: Dict[str, callable] = {}

    def __getattr__(self, name):
        # Only reached for names not set on the wrapper itself.
        return getattr(self.artifact, name)

    # -- live updates ----------------------------------------------------------
    def with_artifact(self, artifact) -> "ShardedArtifact":
        """A wrapper serving ``artifact`` that SHARES this wrapper's mesh
        and jitted shard_map cache.

        This is the sharded half of the online-serving swap contract:
        the artifact is an *operand* of the cached jit functions, so a
        shape-stable new generation hits the already-compiled
        executables (zero recompiles) — but ONLY if the swap reuses the
        same jit objects. A freshly-constructed ``ShardedArtifact``
        would carry a fresh ``_fns`` cache and recompile every method on
        first call. Queries already dispatched against the old wrapper
        keep their old-generation operand — the swap is race-free by
        construction.
        """
        if isinstance(artifact, ShardedArtifact):
            raise TypeError("artifact is already sharded")
        new = ShardedArtifact.__new__(ShardedArtifact)
        new.artifact = artifact
        new.mesh = self.mesh
        new.n_devices = self.n_devices
        new._fns = self._fns  # shared jit objects -> shared compile cache
        return new

    def refresh(self, model) -> "ShardedArtifact":
        """Re-freeze the wrapped artifact from an updated model, keeping
        this wrapper's mesh and compile cache."""
        return self.with_artifact(self.artifact.refresh(model))

    # -- sharded dispatch ------------------------------------------------------
    def _sharded_fn(self, key: str, local):
        """The jitted shard_map of ``local(artifact, rows)``, cached
        under ``key`` (the method name, plus any static args — e.g. the
        top-k width — that the local closure bakes in)."""
        fn = self._fns.get(key)
        if fn is None:
            axis = self.mesh.axis_names[0]
            # check_vma=False: the per-shard body calls Pallas kernels,
            # which have no shard_map replication rule.
            fn = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(), P(axis)), out_specs=P(axis),
                check_vma=False))
            self._fns[key] = fn
        return fn

    def _call(self, key: str, local, feats):
        if not hasattr(feats, "shape"):
            # Preserve the caller's dtype: forcing f32 here would make
            # the sharded path disagree with the single-device artifact
            # (and warm a different jit signature) for non-f32 streams.
            feats = np.asarray(feats)
        n = int(feats.shape[0])
        m = round_up(max(n, 1), self.n_devices)
        # pad_rows is namespace-agnostic: numpy batches pad on the host
        # (off the device queue), device-resident batches stay on device
        # with async dispatch — no forced device->host round-trip.
        out = self._sharded_fn(key, local)(self.artifact,
                                           pad_rows(feats, m))
        # Outputs are row-sharded pytrees (predict: one array; topk: a
        # (classes, ids, sims) triple) — drop the padded tail rows.
        return jax.tree.map(lambda o: o[:n], out)

    def _method_local(self, method: str):
        def local(art, x):
            return getattr(art, method)(x)
        return local

    # -- protocol surface ------------------------------------------------------
    def predict(self, feats) -> Array:
        return self._call("predict", self._method_local("predict"), feats)

    def predict_features(self, feats) -> Array:
        return self._call("predict_features",
                          self._method_local("predict_features"), feats)

    def predict_query(self, q) -> Array:
        return self._call("predict_query",
                          self._method_local("predict_query"), q)

    def predict_topk(self, feats, k: int):
        """Sharded top-k serving (backends exposing ``predict_topk``).

        Returns the wrapped artifact's ((B, k) classes, (B, k) centroid
        ids, (B, k) sims) triple, rows sharded over the mesh — bit-exact
        with the single-device call.
        """
        k = int(k)

        def local(art, x):
            return art.predict_topk(x, k)

        return self._call(f"predict_topk:{k}", local, feats)

    def score(self, feats, labels, batch: int = 4096) -> float:
        from repro.core import evaluate as eval_lib
        return eval_lib.batched_accuracy(self.predict, feats, labels,
                                         batch)

    @property
    def row_multiple(self) -> int:
        """Rows per batch must divide into this many equal shards."""
        return self.n_devices
