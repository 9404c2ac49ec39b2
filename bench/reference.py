"""Plain float32 ``jax.numpy`` reference of what each cell serves or trains.

Independent of the program: it imports nothing of ``repro`` and takes
only what the benchmark made from the seed (features, projection,
centroids, index). ``precision`` is the precision of the projection,
the one float contraction: the configurations state ``HIGHEST``
(float32). The controls run the same code at ``HIGH`` (three bf16
passes) and ``DEFAULT`` (one), spelled out on float32 arithmetic so that
they compute the same on any backend: the features split into one or
two bf16 terms, each multiplied exactly by the bipolar projection.
Products of bipolar operands are exact at any precision.

Semantics, as the configurations state them:

* encode: q = sign(x @ M), with sign(0) = +1;
* flat search: the centroid of highest dot similarity, the lowest index
  among equals; the answer is its owner class;
* hierarchical search: the S super-centroids of highest similarity
  (lowest group id among equals), then the best centroid among the
  members of those groups (lowest centroid id among equals);
* QAIL (paper Sec. III-C): per minibatch, against the current binary
  AM, the mispredicted samples move lr*h onto their true class's best
  centroid and off the predicted one; after each minibatch every float
  centroid is rescaled to the mean centroid norm and the binary AM is
  re-thresholded at the float AM's mean.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    """Round float32 to the nearest bfloat16 value (ties to even), on the
    bits: a compiler may drop a float32 -> bfloat16 -> float32 round trip
    that it is allowed to compute in excess precision."""
    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(x, u32)
    bits = (bits + u32(0x7FFF) + ((bits >> u32(16)) & u32(1))) & u32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def encode(x, proj, precision=HIGHEST):
    """(B, F) features -> (B, D) float hypervectors."""
    if precision == jax.lax.Precision.HIGH:
        hi = _bf16(x)
        return (jnp.dot(hi, proj, precision=HIGHEST)
                + jnp.dot(_bf16(x - hi), proj, precision=HIGHEST))
    if precision == jax.lax.Precision.DEFAULT:
        return jnp.dot(_bf16(x), proj, precision=HIGHEST)
    return jnp.dot(x, proj, precision=HIGHEST)


def query_bits(x, proj, precision=HIGHEST):
    """(B, F) features -> (B, D) bipolar query."""
    return jnp.where(encode(x, proj, precision) >= 0, 1.0, -1.0)


@partial(jax.jit, static_argnames=("precision",))
def flat_classes(x, proj, am, owners, precision=HIGHEST):
    """(B,) answer classes of the flat search over ``am`` (C, D)."""
    sims = query_bits(x, proj, precision) @ am.T
    return owners[jnp.argmax(sims, axis=-1)]


@partial(jax.jit, static_argnames=("shortlist", "precision"))
def hier_classes(x, proj, am, assign, supers, *, shortlist: int,
                 precision=HIGHEST):
    """(B,) answer classes (one class per centroid: the centroid id) of
    the coarse-to-fine search."""
    q = query_bits(x, proj, precision)
    g = supers.shape[0]
    # Integer similarities are exact in float32; the id term breaks
    # ties toward the lower group without changing the order otherwise.
    coarse = (q @ supers.T) * g + (g - 1 - jnp.arange(g))
    _, short = jax.lax.top_k(coarse, shortlist)             # (B, S)
    chosen = jnp.zeros((x.shape[0], g), bool).at[
        jnp.arange(x.shape[0])[:, None], short].set(True)   # (B, G)
    sims = jnp.where(chosen[:, assign], q @ am.T, -jnp.inf)
    return jnp.argmax(sims, axis=-1).astype(jnp.int32)


def blocked(fn, x, block: int, *args, **kw):
    """Apply ``fn`` to ``x`` in row blocks (keeps the (B, C) sims small)."""
    return np.concatenate([np.asarray(fn(x[i:i + block], *args, **kw))
                           for i in range(0, x.shape[0], block)])


# -- QAIL ----------------------------------------------------------------------

def _threshold(fp):
    return jnp.where(fp > jnp.mean(fp), 1.0, -1.0)


def _equalize(fp):
    norm = jnp.linalg.norm(fp, axis=-1, keepdims=True)
    return fp * (jnp.mean(norm) / jnp.maximum(norm, 1e-8))


@partial(jax.jit, static_argnames=("epochs", "batch", "precision"))
def qail(fp, owners, x, y, proj, *, epochs: int, batch: int, lr: float,
         precision=HIGHEST):
    """``epochs`` QAIL epochs over (x, y) in order, minibatches of
    ``batch`` (the last one padded with samples that never update).

    Returns (fp, binary, misses (epochs,) float32).
    """
    h = encode(x, proj, precision)
    q = jnp.where(h >= 0, 1.0, -1.0)
    n = x.shape[0]
    nb = -(-n // batch)
    pad = nb * batch - n
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, batch, -1)
    qb = jnp.pad(q, ((0, pad), (0, 0))).reshape(nb, batch, -1)
    yb = jnp.pad(y, (0, pad), constant_values=-1).reshape(nb, batch)
    valid = (jnp.arange(nb * batch) < n).astype(jnp.float32).reshape(
        nb, batch)

    def step(carry, xs):
        fp, binary = carry
        hx, qx, yx, vx = xs
        sims = qx @ binary.T
        pred = jnp.argmax(sims, axis=-1)
        miss = (owners[pred] != yx).astype(jnp.float32) * vx
        true = jnp.argmax(jnp.where(owners[None, :] == yx[:, None], sims,
                                    -jnp.inf), axis=-1)
        coef = (lr * miss)[:, None] * hx
        fp = fp.at[true].add(coef).at[pred].add(-coef)
        fp = _equalize(fp)
        return (fp, _threshold(fp)), miss.sum()

    def epoch(carry, _):
        carry, misses = jax.lax.scan(step, carry, (hb, qb, yb, valid))
        return carry, misses.sum()

    (fp, binary), misses = jax.lax.scan(epoch, (fp, _threshold(fp)),
                                        None, length=epochs)
    return fp, binary, misses
