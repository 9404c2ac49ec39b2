"""Step-function builders: train_step and serve_step under pjit.

``make_train_step`` closes over (ModelConfig, AdamWConfig, schedule,
ShardingRules) and returns a pure (params, opt_state, batch, step) ->
(params, opt_state, metrics) function whose activations are annotated
with the rules' logical shardings. XLA GSPMD inserts every collective;
the dry-run inspects them.

Distributed-optimization features wired here:
  * FSDP / TP via the rules (params sharded at rest, gathered per layer).
  * DeepSeek-V3 aux-free router balancing: router biases are updated
    outside the gradient with the batch's expert counts.
  * Optional int8 error-feedback gradient compression across the "pod"
    axis (shard_map ring reduce-scatter; see optim/compression.py).
    With compression ON the gradient is averaged over pods *manually*,
    so the loss is computed with gradients stopped from crossing pods
    (per-pod mean), matching what the wire carries.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.sharding import ShardingRules, use_rules
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.optim.compression import _BLOCK, \
    ef_int8_compress, ring_all_gather, ring_reduce_scatter_int8

Array = jax.Array
PyTree = Any

BIAS_UPDATE_RATE = 0.001  # DeepSeek-V3 gamma for aux-free balancing


def _apply_router_bias_update(params: PyTree, cfg: ModelConfig,
                              metrics: Dict[str, Array]) -> PyTree:
    """Aux-free load balancing: bias += gamma * sign(mean_load - load)."""
    groups = list(params["groups"])
    for gi, (b, gp) in enumerate(zip(cfg.blocks, groups)):
        key = f"expert_counts_g{gi}"
        if b.ffn.kind != "moe" or b.ffn.router != "sigmoid" \
                or key not in metrics:
            continue
        counts = metrics[key]
        err = jnp.mean(counts) - counts
        new_bias = gp["ffn"]["router_bias"] \
            + BIAS_UPDATE_RATE * jnp.sign(err)
        gp = dict(gp, ffn=dict(gp["ffn"], router_bias=new_bias))
        groups[gi] = gp
    return dict(params, groups=groups)


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    schedule: Callable[[Array], Array],
                    rules: Optional[ShardingRules] = None,
                    grad_compression: str = "none",
                    grad_accum: int = 1,
                    ) -> Callable:
    """Build the train step (not yet jitted — callers own jit options).

    ``grad_accum`` > 1 splits the global batch into that many
    microbatches and accumulates gradients in an f32 buffer (scan) —
    the live-activation footprint shrinks by the same factor, which is
    what lets the 340B/671B train cells fit a 16 GB/chip pod.
    """

    def _grads(params, batch):
        return jax.value_and_grad(T.loss_fn, has_aux=True)(
            params, cfg, batch)

    def train_step(params, opt_state, batch, step):
        with use_rules(rules):
            if grad_accum == 1:
                (loss, metrics), grads = _grads(params, batch)
            else:
                micro = jax.tree.map(
                    lambda x: x.reshape((grad_accum,
                                         x.shape[0] // grad_accum)
                                        + x.shape[1:]), batch)

                def body(acc, mb):
                    (l, m), g = _grads(params, mb)
                    acc = jax.tree.map(
                        lambda a, gi: a + gi.astype(jnp.float32) /
                        grad_accum, acc, g)
                    return acc, (l, m)

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                grads, (losses, metricses) = jax.lax.scan(
                    body, g0, micro)
                loss = losses.mean()
                # Scalars average; expert counts sum over microbatches.
                metrics = {
                    k: (jnp.sum(v, axis=0)
                        if k.startswith("expert_counts")
                        else jnp.mean(v, axis=0))
                    for k, v in metricses.items()}
                metrics["loss"] = loss
            if grad_compression == "int8_ef":
                grads, opt_state = _compress_pod_grads(grads, opt_state)
            lr_scale = schedule(step)
            new_params, new_opt = adamw_update(
                params, grads, opt_state, opt_cfg, lr_scale)
            new_params = _apply_router_bias_update(new_params, cfg, metrics)
        metrics = {k: v for k, v in metrics.items()
                   if not k.startswith("expert_counts")}
        metrics["grad_step"] = step + 1
        return new_params, new_opt, metrics

    return train_step


def _compress_pod_grads(grads: PyTree, opt_state: PyTree,
                        ) -> Tuple[PyTree, PyTree]:
    """Int8 error-feedback all-reduce of grads across the "pod" axis.

    Requires running inside shard_map over "pod" — wired by
    make_compressed_train_step below. Error-feedback buffers live in
    opt_state["ef_err"] (same tree as grads).
    """
    err_tree = opt_state.get("ef_err")
    if err_tree is None:
        raise ValueError("opt_state lacks ef_err buffers; "
                         "init with init_ef_buffers()")
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(err_tree)
    out_g, out_e = [], []
    n = jax.lax.axis_size("pod")
    for g, e in zip(flat_g, flat_e):
        q, scale, new_err = ef_int8_compress(g, e)
        deq = q.astype(jnp.float32) * scale
        pad = -deq.shape[0] % n
        deq_p = jnp.pad(deq, ((0, pad), (0, 0)))
        red = ring_reduce_scatter_int8(deq_p, "pod")
        full = ring_all_gather(red, "pod")
        flat = full.reshape(-1)[: g.size] / n
        out_g.append(flat.reshape(g.shape).astype(g.dtype))
        out_e.append(new_err)
    return (treedef.unflatten(out_g),
            dict(opt_state, ef_err=treedef.unflatten(out_e)))


def init_ef_buffers(params: PyTree) -> PyTree:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_serve_step(cfg: ModelConfig,
                    rules: Optional[ShardingRules] = None) -> Callable:
    """One-token decode step: (params, batch, caches) -> (logits, caches)."""

    def serve_step(params, batch, caches):
        with use_rules(rules):
            return T.decode_step(params, cfg, batch, caches)

    return serve_step


def init_train_state(key, cfg: ModelConfig, opt_cfg: AdamWConfig,
                     ) -> Tuple[PyTree, PyTree, PyTree]:
    """(params, opt_state, logical_axes) — host-side init for real runs."""
    params, axes = T.init_params(key, cfg)
    opt_state = adamw_init(params, opt_cfg)
    return params, opt_state, axes


def abstract_train_state(cfg: ModelConfig, opt_cfg: AdamWConfig, seed: int = 0,
                         ) -> Tuple[PyTree, PyTree, PyTree]:
    """ShapeDtypeStruct versions for the dry-run (zero allocation)."""
    from repro.models import layers as L
    with L.abstract_init():
        params_shape, axes = T.init_params(jax.random.key(seed), cfg)
    opt_shape = jax.eval_shape(lambda p: adamw_init(p, opt_cfg),
                               params_shape)
    return params_shape, opt_shape, axes
