"""Build the system under test for a configuration, from the seed.

A configuration's ``system`` key names its function here:

* ``sampled_flat`` — a projection encoder and an AM of C class-balanced
  sampled training rows (``gen.sampled_am``), frozen through the deploy
  registry (``model.deploy(target=backend)``);
* ``planted_hierarchical`` — a planted label space of C centroids over
  G prototypes (``gen.planted_index``), frozen into the coarse-to-fine
  artifact with the planted groups as its index.

The benchmark makes every weight and table, and hands the same arrays
to the program and to the reference (``bench.reference``), so that the
reference takes nothing the program made. The program's own index
build (``deploy.hierarchical.cluster_am``) is therefore not run: the
artifact is laid out by ``deploy.hierarchical.build_layout`` from the
planted groups.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference

# Key streams of one seed.
PROJ, POOL, AM, TRAIN, CLASSES = 1, 2, 3, 4, 5


@dataclasses.dataclass
class System:
    """What a mode needs of a configuration."""

    cfg: dict
    model: object            # repro MemhdModel (encoder + AM state)
    artifact: object         # the deployed serving artifact
    proj: jax.Array          # (F, D) projection, as made by the benchmark
    rows: Callable[[int], np.ndarray]       # n -> (n, F) host feature pool
    answers: Callable[..., np.ndarray]      # (x, precision) -> classes
    # (x, y, fp0, owners, model holding fp0) for training configurations
    train: Optional[tuple] = None


def _model(cfg, proj, fp, owners):
    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    from repro.core import am as am_lib

    q = cfg.get("qail", {})
    enc = EncoderConfig(kind="projection", features=cfg["features"],
                        dim=cfg["dim"])
    amc = MemhdConfig(dim=cfg["dim"], columns=cfg["columns"],
                      classes=cfg["classes"], **q)
    return MemhdModel({"projection": proj}, am_lib.make_am_state(fp, owners),
                      enc, amc)


def sampled_flat(cfg: dict, seed: int) -> System:
    d = cfg["data"]
    proj = gen.projection(gen.seed_key(seed, PROJ), features=cfg["features"],
                          dim=cfg["dim"])
    shape = dict(features=cfg["features"], classes=cfg["classes"],
                 modes=d["latent_modes"])
    classes = gen.seed_key(seed, CLASSES)
    tx, ty = gen.feature_rows(classes, gen.seed_key(seed, TRAIN),
                              n=d["train_rows"], **shape)
    fp, binary, owners = gen.sampled_am(gen.seed_key(seed, AM), tx, ty, proj,
                                        columns=cfg["columns"],
                                        classes=cfg["classes"])
    # The served model holds the binary AM as its float AM, so that the
    # program's mean threshold reproduces it exactly.
    model = _model(cfg, proj, binary, owners)
    artifact = model.deploy(target=cfg["backend"], **cfg.get("deploy", {}))
    train_model = _model(cfg, proj, fp, owners)

    def rows(n):
        # Fresh samples of the training rows' classes.
        x, _ = gen.feature_rows(classes, gen.seed_key(seed, POOL), n=n,
                                **shape)
        return np.asarray(x)

    def answers(x, precision=reference.HIGHEST):
        return reference.blocked(reference.flat_classes, x, 4096, proj,
                                 binary, owners, precision=precision)

    return System(cfg, model, artifact, proj, rows, answers,
                  train=(tx, ty, fp, owners, train_model))


def planted_hierarchical(cfg: dict, seed: int) -> System:
    from repro.core import am as am_lib
    from repro.deploy.hierarchical import HierarchicalMemhd, build_layout

    d = cfg["data"]
    g, s = cfg["deploy"]["groups"], cfg["deploy"]["shortlist"]
    proj = gen.projection(gen.seed_key(seed, PROJ), features=cfg["features"],
                          dim=cfg["dim"])
    idx = gen.planted_index(gen.seed_key(seed, AM), proj, groups=g,
                            columns=cfg["columns"],
                            proto_sigma=d["proto_sigma"],
                            proto_flip=d["proto_flip"])
    owners = jnp.arange(cfg["columns"], dtype=jnp.int32)
    model = _model(cfg, proj, idx.am, owners)
    layout = build_layout(np.asarray(am_lib.pack_am(idx.am)),
                          np.asarray(idx.assign), g)
    artifact = HierarchicalMemhd(
        enc_params=model.enc_params,
        super_packed_t=jnp.asarray(np.asarray(am_lib.pack_am(idx.supers))),
        am_slab_t=jnp.asarray(layout.slab),
        col_ids=jnp.asarray(layout.col_ids),
        tile_start=jnp.asarray(layout.tile_start),
        tile_count=jnp.asarray(layout.tile_count),
        centroid_class=owners, enc_cfg=model.enc_cfg, am_cfg=model.am_cfg,
        groups=g, shortlist=s, max_tiles=layout.max_tiles)

    def rows(n):
        return np.asarray(gen.planted_rows(
            gen.seed_key(seed, POOL), idx.proto_raw, n=n,
            noise_sigma=d["query_noise"]))

    def answers(x, precision=reference.HIGHEST):
        return reference.blocked(reference.hier_classes, x, 512, proj,
                                 idx.am, idx.assign, idx.supers,
                                 shortlist=s, precision=precision)

    return System(cfg, model, artifact, proj, rows, answers)


SYSTEMS = {"sampled_flat": sampled_flat,
            "planted_hierarchical": planted_hierarchical}


def build(cfg: dict, seed: int) -> System:
    if cfg["system"] not in SYSTEMS:
        raise ValueError(f"unknown system {cfg['system']!r}; known: "
                         f"{sorted(SYSTEMS)}")
    return SYSTEMS[cfg["system"]](cfg, seed)
