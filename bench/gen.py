"""The benchmark's one traffic and data generator, driven by data files.

Everything a run feeds the system is made here from ``--seed``: the
feature rows, the weights (projection and associative memory), the
request schedule. The traffic file (``bench/traffic/<name>.json``)
gives the parameters; the configuration file gives the shapes.

Copied from the program rather than imported, so that a change to the
program cannot change what the benchmark offers it:

* ``feature_rows`` follows ``repro.data.hdc.synthesize``: each class is
  a mixture of latent modes, a sample is its mode's sparse template plus
  Gaussian noise, squashed into [0, 1] by a sigmoid. Here it runs on the
  device in one jitted call.
* ``planted_index`` follows ``benchmarks.hierarchical_search.planted_am``:
  centroids are prototype hypervectors with a fixed bit-flip rate.
* ``arrival_schedule`` follows ``repro.serve.stream.poisson_arrivals``:
  an open loop with exponential gaps and 1..max rows per request. Every
  seed gets the same multiset of gaps and sizes, in another order, so
  that seeds change the order of the work and not its amount.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int, salt: int) -> jax.Array:
    """A JAX key for ``seed`` (any size) and a stream ``salt``."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), salt)


def host_rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), salt])


# -- features ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n", "features", "classes", "modes"))
def feature_rows(template_key, key, *, n: int, features: int, classes: int,
                 modes: int,
                 common_sigma: float = 0.55, common_density: float = 0.12,
                 mode_sigma: float = 1.9, mode_density: float = 0.15,
                 noise_sigma: float = 0.72):
    """(n, features) float32 rows in [0, 1] and (n,) int32 labels.

    Labels are balanced (``n % classes == 0``) and shuffled; modes are
    drawn uniformly. The class templates depend on ``template_key`` only:
    rows drawn with one template key and different ``key`` are samples
    of the same classes.
    """
    kc, km, kd = jax.random.split(template_key, 3)
    ks, kn, kp = jax.random.split(key, 3)
    common = (jax.random.normal(kc, (classes, features)) * common_sigma
              * (jax.random.uniform(kd, (classes, features))
                 < common_density))
    k1, k2 = jax.random.split(km)
    delta = (jax.random.normal(k1, (classes, modes, features)) * mode_sigma
             * (jax.random.uniform(k2, (classes, modes, features))
                < mode_density))
    templates = common[:, None, :] + delta
    labels = jax.random.permutation(kp, jnp.arange(n, dtype=jnp.int32)
                                    % classes)
    mode = jax.random.randint(ks, (n,), 0, modes)
    raw = templates[labels, mode] + noise_sigma * jax.random.normal(
        kn, (n, features))
    return jax.nn.sigmoid(raw).astype(jnp.float32), labels


@partial(jax.jit, static_argnames=("features", "dim"))
def projection(key, *, features: int, dim: int):
    """(features, dim) bipolar float32 projection matrix."""
    return jax.random.rademacher(key, (features, dim), dtype=jnp.float32)


# -- associative memories ------------------------------------------------------

@partial(jax.jit, static_argnames=("columns", "classes"))
def sampled_am(key, x, y, proj, *, columns: int, classes: int):
    """Class-balanced random-sampling AM (the paper's baseline init).

    ``columns`` training rows, as even over the classes as C allows,
    are encoded at float32 precision; the float rows are the shadow AM
    and their mean-threshold signs the binary AM. Returns
    (fp (C, D), binary (C, D) bipolar, owners (C,) int32).
    """
    n = x.shape[0]
    per = n // classes
    # Rows grouped by class in a random order within each class.
    order = jnp.lexsort((jax.random.uniform(key, (n,)), y))
    owners = jnp.arange(columns, dtype=jnp.int32) % classes
    rank = jnp.arange(columns, dtype=jnp.int32) // classes
    rows = order[owners * per + rank]
    fp = jnp.dot(x[rows], proj, precision=HIGHEST)
    binary = jnp.where(fp > jnp.mean(fp), 1.0, -1.0).astype(jnp.float32)
    return fp, binary, owners


@dataclasses.dataclass(frozen=True)
class PlantedIndex:
    """A planted label space and its coarse index, all on the device."""

    proto_raw: jax.Array   # (G, F) pre-sigmoid prototype features
    am: jax.Array          # (C, D) bipolar centroids
    assign: jax.Array      # (C,) int32 group of each centroid
    supers: jax.Array      # (G, D) bipolar majority vote of each group


@partial(jax.jit, static_argnames=("features", "groups", "columns"))
def _planted(key, proj, *, features, groups, columns, proto_sigma,
             proto_flip):
    kr, ka, kf = jax.random.split(key, 3)
    proto_raw = proto_sigma * jax.random.normal(kr, (groups, features))
    protos = jnp.where(jnp.dot(jax.nn.sigmoid(proto_raw), proj,
                               precision=HIGHEST) >= 0, 1.0, -1.0)
    assign = jax.random.randint(ka, (columns,), 0, groups, jnp.int32)
    flip = jax.random.uniform(kf, (columns, proj.shape[1])) < proto_flip
    am = jnp.where(flip, -protos[assign], protos[assign])
    votes = jax.ops.segment_sum(am, assign, num_segments=groups)
    supers = jnp.where(votes >= 0, 1.0, -1.0)
    return proto_raw, am.astype(jnp.float32), assign, supers


def planted_index(key, proj, *, groups: int, columns: int,
                  proto_sigma: float, proto_flip: float) -> PlantedIndex:
    """G prototypes in feature space, their encoded signs as planted
    hypervectors, C centroids each a prototype with ``proto_flip`` of
    its bits flipped, and the index over them: group = planted
    prototype, super-centroid = the bit majority of the group's
    members (ties to +1), as ``deploy.hierarchical.cluster_am`` votes."""
    return PlantedIndex(*_planted(
        key, proj, features=proj.shape[0], groups=groups, columns=columns,
        proto_sigma=proto_sigma, proto_flip=proto_flip))


@partial(jax.jit, static_argnames=("n",))
def planted_rows(key, proto_raw, *, n: int, noise_sigma: float):
    """(n, F) rows near the prototypes, the groups balanced and shuffled."""
    kg, kn = jax.random.split(key)
    g = jax.random.permutation(
        kg, jnp.arange(n, dtype=jnp.int32) % proto_raw.shape[0])
    raw = proto_raw[g] + noise_sigma * jax.random.normal(
        kn, (n, proto_raw.shape[1]))
    return jax.nn.sigmoid(raw).astype(jnp.float32)


# -- request schedules ---------------------------------------------------------

def arrival_schedule(seed: int, *, seconds: float, rate_rps: float,
                     rows_min: int, rows_max: int, pool_rows: int):
    """Open-loop arrivals for a window of ``seconds``.

    Returns (t_arrival (n,), sizes (n,), starts (n,)) with
    n = round(rate * seconds): request i is due at t_arrival[i] seconds
    and asks for pool rows starts[i] .. starts[i] + sizes[i]. The gaps
    are the n exponential quantiles of mean 1/rate and the sizes an even
    spread over rows_min..rows_max, both shuffled by the seed; the
    starts are uniform over the pool.
    """
    n = max(1, int(round(rate_rps * seconds)))
    rng = host_rng(seed, 11)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate_rps)
    span = rows_max - rows_min + 1
    sizes = rng.permutation(rows_min + np.arange(n) % span)
    t = np.cumsum(gaps)
    t *= seconds / t[-1]  # the last request is due at the window's end
    starts = rng.integers(0, pool_rows - rows_max + 1, size=n)
    return t, sizes.astype(np.int64), starts


def bulk_order(seed: int, call: int, n_requests: int) -> np.ndarray:
    """Order in which call ``call`` offers the pool's requests."""
    return host_rng(seed, 1000 + call).permutation(n_requests)
