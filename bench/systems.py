"""Build the system under test for a configuration, from the seed.

A configuration's ``system`` key names its builder,
``bench/builders/<system>.py``, which exports
``build(cfg, seed) -> System``; ``build`` here imports it by that name.
What the builders share is here: ``System``, ``_model`` and the key
streams of one seed.

The benchmark makes every weight and table, and hands the same arrays
to the program and to the reference (``bench.reference``), so that the
reference takes nothing the program made.
"""
from __future__ import annotations

import dataclasses
import importlib
import re
from pathlib import Path
from typing import Callable, Optional

import jax
import numpy as np

# Key streams of one seed.
PROJ, POOL, AM, TRAIN, CLASSES = 1, 2, 3, 4, 5

BUILDERS = Path(__file__).resolve().parent / "builders"
# A builder's name: a module name of at most 64 characters that starts
# with a letter, so that it names no file outside ``bench/builders/``
# nor the package's ``__init__``.
NAME = re.compile(r"[A-Za-z][A-Za-z0-9_]{0,63}")


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


def build(cfg: dict, seed: int) -> System:
    name = cfg["system"]
    if not (NAME.fullmatch(name) and (BUILDERS / f"{name}.py").is_file()):
        known = sorted(p.stem for p in BUILDERS.glob("*.py")
                       if NAME.fullmatch(p.stem))
        raise ValueError(f"unknown system {name!r}; known: {known}")
    return importlib.import_module(f"bench.builders.{name}").build(cfg, seed)
