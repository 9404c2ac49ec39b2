"""A planted label space of C centroids over G prototypes
(``gen.planted_index``), frozen into the coarse-to-fine artifact with
the planted groups as its index.

The program's own index build (``deploy.hierarchical.cluster_am``) is
not run: the artifact is laid out by ``deploy.hierarchical.build_layout``
from the planted groups, so that the reference gets the same index."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import gen, reference
from bench.systems import AM, POOL, PROJ, System, _model


def build(cfg: dict, seed: int) -> System:
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
