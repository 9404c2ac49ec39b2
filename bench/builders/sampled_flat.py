"""A projection encoder and an AM of C class-balanced sampled training
rows (``gen.sampled_am``), frozen through the deploy registry
(``model.deploy(target=backend)``)."""
from __future__ import annotations

import numpy as np

from bench import gen, reference
from bench.systems import AM, CLASSES, POOL, PROJ, TRAIN, System, _model


def build(cfg: dict, seed: int) -> System:
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
