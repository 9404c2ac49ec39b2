"""Operations and bytes of the algorithm, from a configuration's shapes.

Counted from what the algorithm needs, not from what a kernel happens
to do, so that the count is the same whatever kernel implements it:
the projection's multiply-adds and the bipolar products of a search as
2 operations each, and the bytes of the operands in their packed form
(float32 features, one bit per hypervector or centroid cell), the
results, and the float rows QAIL updates. A kernel's roofline share is
then the least time the chip needs for that work, the larger of
operations over peak and bytes over bandwidth, over the device time the
kernel took. Peaks come from ``peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _bits(n: int) -> int:
    return math.ceil(n / 8)


def encode_pack(b: int, f: int, d: int) -> tuple[float, float]:
    """Projection of b rows, signs packed: (ops, bytes)."""
    return 2.0 * b * f * d, 4.0 * b * f + _bits(f * d) + b * _bits(d)


def search_flat(b: int, d: int, c: int) -> tuple[float, float]:
    """Packed search of b queries over C centroids, best index out."""
    return 2.0 * b * d * c, b * _bits(d) + c * _bits(d) + 8.0 * b


def shortlist(b: int, d: int, g: int, s: int) -> tuple[float, float]:
    """Top-s of g super-centroids for b queries."""
    return 2.0 * b * d * g, b * _bits(d) + g * _bits(d) + 8.0 * b * s


def rerank(b: int, d: int, c: int, g: int, s: int) -> tuple[float, float]:
    """Re-rank of the s shortlisted groups' members (C/G a group)."""
    cols = s * math.ceil(c / g)
    return 2.0 * b * d * cols, b * _bits(d) + b * cols * _bits(d) + 8.0 * b


def qail_step(b: int, d: int, c: int) -> tuple[float, float]:
    """One QAIL minibatch: sims against the binary AM, and each sample's
    push and pull of a float centroid row (read and written)."""
    ops = 2.0 * b * d * c + 4.0 * b * d
    nbytes = (b * _bits(d) + 4.0 * b * d + c * _bits(d)
              + 2 * 2 * min(2 * b, c) * 4.0 * d)
    return ops, nbytes


def serve_row_ops(cfg: dict) -> float:
    """Operations of serving one row: 2FD + 2DC flat, and
    2FD + 2D(G + S*ceil(C/G)) hierarchical."""
    f, d, c = cfg["features"], cfg["dim"], cfg["columns"]
    if cfg["backend"] == "hierarchical":
        g, s = cfg["deploy"]["groups"], cfg["deploy"]["shortlist"]
        return 2.0 * f * d + 2.0 * d * (g + s * math.ceil(c / g))
    return 2.0 * f * d + 2.0 * d * c


def train_sample(cfg: dict) -> float:
    """Operations of one sample in one QAIL epoch (the encode is made
    once per training set and not counted per epoch)."""
    d, c = cfg["dim"], cfg["columns"]
    return 2.0 * d * c + 4.0 * d


def roofline_s(ops: float, nbytes: float, peak: dict) -> float:
    """Least time for the work on a chip with ``peak``."""
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
