"""``"frontend"`` backend: token rows in, classes out, through a frozen LM.

A language model's prefill runs as a feature extractor
(``models.transformer.pooled_features``: the layer groups, the final
RMSNorm, the mean over each row's positions, in bfloat16) in front of
MEMHD's packed AM: the pooled rows are the feature rows that the fused
encoder binarizes and packs (``encode_pack`` takes them as bfloat16, one
MXU pass) and the packed search classifies. The whole chain is one jit.

A row's output is one int32 row (``FrontendMemhd.layout``): the class,
the packed query (D/32 words, LSB-first), the row's slots routed to each
held expert of each MoE layer, the row's held slots left uncomputed
(0: the held-range MoE layer is dropless) and the row's token count.
``serve_batches`` slices it by rows as it does classes, and hands each
drained batch's rows to ``record_batch``, which feeds the front end's
counters.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.deploy.base import DeployedArtifact, pytree_artifact
from repro.deploy.registry import register_backend

Array = jax.Array


@pytree_artifact
@dataclasses.dataclass
class FrontendMemhd(DeployedArtifact):
    """A frozen LM front end feeding a packed MEMHD artifact."""

    lm_params: Dict[str, Any]
    memhd: Any                     # packed DeployedMemhd
    lm_cfg: Any                    # models.config.ModelConfig (aux)

    _leaf_fields: ClassVar[Tuple[str, ...]] = ("lm_params", "memhd")
    _static_fields: ClassVar[Tuple[str, ...]] = ("lm_cfg",)

    # The protocol's MEMHD fields are the packed artifact's.
    enc_params = property(lambda self: self.memhd.enc_params)
    centroid_class = property(lambda self: self.memhd.centroid_class)
    enc_cfg = property(lambda self: self.memhd.enc_cfg)
    am_cfg = property(lambda self: self.memhd.am_cfg)

    @property
    def moe_layers(self) -> int:
        return sum(b.repeat for b in self.lm_cfg.blocks
                   if b.ffn.kind == "moe" and b.ffn.n_held)

    @property
    def n_held(self) -> int:
        return max((b.ffn.n_held for b in self.lm_cfg.blocks
                    if b.ffn.kind == "moe"), default=0)

    @property
    def layout(self) -> Dict[str, slice]:
        """Column slices of an output row."""
        words = -(-self.am_cfg.dim // 32)
        n = self.moe_layers * self.n_held
        return {"cls": slice(0, 1), "query": slice(1, 1 + words),
                "counts": slice(1 + words, 1 + words + n),
                "dropped": slice(1 + words + n, 2 + words + n),
                "tokens": slice(2 + words + n, 3 + words + n)}

    # -- inference -------------------------------------------------------------
    def predict_features(self, tokens: Array) -> Array:
        """(B, S) int32 token rows -> (B, ``layout`` width) int32."""
        return _frontend_rows(self.lm_params, self.memhd, tokens,
                              lm_cfg=self.lm_cfg)

    def predict(self, tokens: Array) -> Array:
        return self.predict_features(tokens)

    def record_batch(self, out: np.ndarray) -> None:
        """Count one drained batch's work from its output rows: tokens
        served, routed slots on this device's experts by layer, the
        busiest held expert's slots by layer, and dropped slots."""
        from repro import obs
        with obs.span("frontend.record", rows=int(out.shape[0])):
            lay = self.layout
            obs.counter("frontend_tokens_total",
                        "token positions served by the front end"
                        ).inc(int(out[:, lay["tokens"]].sum()))
            counts = out[:, lay["counts"]].reshape(
                out.shape[0], self.moe_layers, self.n_held).sum(axis=0)
            local = obs.counter("moe_local_slots_total",
                                "routed slots on this device's experts")
            busiest = obs.gauge("moe_expert_slots_max",
                                "slots of the busiest held expert in the "
                                "last batch")
            for layer, per_expert in enumerate(counts):
                local.inc(int(per_expert.sum()), layer=layer)
                busiest.set(int(per_expert.max()), layer=layer)
            obs.counter("moe_dropped_slots_total",
                        "held slots left uncomputed (dropless: 0)"
                        ).inc(int(out[:, lay["dropped"]].sum()))

    # -- reporting / accounting ------------------------------------------------
    @property
    def backend(self) -> str:
        return "frontend"

    @property
    def serving_mode(self) -> str:
        return f"{self.lm_cfg.name}+{self.memhd.serving_mode}"

    @property
    def resident_bytes(self) -> int:
        return self.memhd.resident_bytes


@functools.partial(jax.jit, static_argnames=("lm_cfg",))
def _frontend_rows(lm_params, memhd, tokens, *, lm_cfg):
    from repro.kernels import ops
    from repro.models import transformer
    feats, aux = transformer.pooled_features(lm_params, lm_cfg, tokens)
    proj = memhd.enc_params["projection"]
    qp = ops.encode_pack(feats, proj)                      # (B, Dp) uint8
    idx, _ = ops.am_search_packed(qp, memhd.am_packed_t,
                                  n_dims=proj.shape[1], mode=memhd.mode)
    b, s = tokens.shape
    words = jnp.pad(qp, ((0, 0), (0, -qp.shape[1] % 4)))
    words = jax.lax.bitcast_convert_type(words.reshape(b, -1, 4), jnp.int32)
    counts = aux.get("held_counts", jnp.zeros((b, 0, 0), jnp.int32))
    dropped = aux.get("dropped", jnp.zeros((b,), jnp.int32))
    return jnp.concatenate(
        [memhd.centroid_class[idx][:, None], words,
         counts.reshape(b, -1), dropped[:, None],
         jnp.full((b, 1), s, jnp.int32)], axis=1).astype(jnp.int32)


@register_backend("frontend")
def deploy_frontend(model, *, lm_params, lm_cfg, mode: str = "popcount",
                    ) -> FrontendMemhd:
    """Pack ``model``'s AM and put the LM ``(lm_params, lm_cfg)`` in
    front of it. The encoder's bipolar projection is held as bfloat16,
    exactly; ``encode_pack`` casts it to the pooled rows' dtype."""
    from repro.deploy.digital import deploy_packed
    memhd = deploy_packed(model, mode=mode)
    proj = memhd.enc_params["projection"].astype(jnp.bfloat16)
    memhd = dataclasses.replace(memhd,
                                enc_params=dict(memhd.enc_params,
                                                projection=proj))
    return FrontendMemhd(lm_params=lm_params, memhd=memhd, lm_cfg=lm_cfg)
