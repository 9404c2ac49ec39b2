"""Operation and byte counts at small shapes, and the peaks table."""
import json

import pytest

from bench import work


def test_counts_at_small_shapes():
    # Projection of 2 rows of 3 features into D = 16: 2*2*3*16 ops;
    # 2 rows of float32 features, 3*16 projection bits, 2 rows of 2 bytes.
    assert work.encode_pack(2, 3, 16) == (192.0, 24.0 + 6 + 4)
    # 2 queries x 5 centroids x D = 16 bipolar products; 2 + 5 packed
    # rows of 2 bytes, 8 bytes of result a query.
    assert work.search_flat(2, 16, 5) == (320.0, 4 + 10 + 16.0)
    assert work.shortlist(2, 16, 4, 2) == (256.0, 4 + 8 + 32.0)
    # 2 of 4 groups over C = 10: ceil(10/4) = 3 members a group.
    assert work.rerank(2, 16, 10, 4, 2) == (2.0 * 2 * 16 * 6,
                                            4 + 2 * 6 * 2 + 16.0)
    ops, nbytes = work.qail_step(2, 16, 5)
    assert ops == 2.0 * 2 * 16 * 5 + 4 * 2 * 16
    assert nbytes == 4 + 128 + 10 + 2 * 2 * 4 * 4 * 16


def test_per_row_and_per_sample_ops():
    flat = {"features": 3, "dim": 16, "columns": 5, "backend": "packed"}
    assert work.serve_row_ops(flat) == 2 * 3 * 16 + 2 * 16 * 5
    hier = dict(flat, columns=10, backend="hierarchical",
                deploy={"groups": 4, "shortlist": 2})
    assert work.serve_row_ops(hier) == 2 * 3 * 16 + 2 * 16 * (4 + 2 * 3)
    assert work.train_sample(flat) == 2 * 16 * 5 + 4 * 16


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000.0, 50.0, peak) == 10.0
    assert work.roofline_s(100.0, 50.0, peak) == 5.0


def test_peaks_keyed_by_device_kind():
    table = json.loads(work.PEAKS.read_text())
    assert "TPU v5e" in table["source"]
    v5e = work.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
