"""The program's outputs equal the benchmark's plain reference, on the
CPU at small sizes, for each path a cell times."""
import numpy as np
import pytest

from bench import reference, systems

SMALL_FLAT = {
    "system": "sampled_flat", "features": 64, "dim": 128, "columns": 64,
    "classes": 4, "backend": "packed", "deploy": {"mode": "popcount"},
    "qail": {"lr": 0.02, "batch_size": 32},
    "data": {"latent_modes": 3, "train_rows": 200},
}
SMALL_HIER = {
    "system": "planted_hierarchical", "features": 64, "dim": 256,
    "columns": 3000, "classes": 3000, "backend": "hierarchical",
    "deploy": {"groups": 12, "shortlist": 3},
    "data": {"proto_sigma": 1.5, "proto_flip": 0.08, "query_noise": 0.5},
}


@pytest.mark.parametrize("cfg", [SMALL_FLAT, SMALL_HIER],
                         ids=["flat", "hierarchical"])
def test_served_classes_equal_reference(cfg):
    system = systems.build(cfg, seed=2**31 + 7)
    x = system.rows(96)
    got = np.asarray(system.artifact.predict_features(x))
    want = system.answers(x)
    np.testing.assert_array_equal(got, want)


def test_qail_epochs_equal_reference():
    import jax

    system = systems.build(SMALL_FLAT, seed=12345)
    x, y, fp0, owners, model = system.train
    q = SMALL_FLAT["qail"]
    fp_r, bin_r, miss_r = reference.qail(
        fp0, owners, x, y, system.proj, epochs=3, batch=q["batch_size"],
        lr=q["lr"])
    trained, hist = model.fit(jax.random.key(0), x, y, init_method="keep",
                              epochs=3, use_kernel=True)
    misses = [round(r["train_miss"] * x.shape[0]) for r in hist["curve"]]
    np.testing.assert_array_equal(misses, np.asarray(miss_r))
    np.testing.assert_array_equal(np.asarray(trained.am_state["binary"]),
                                  np.asarray(bin_r))
    np.testing.assert_allclose(np.asarray(trained.am_state["fp"]),
                               np.asarray(fp_r), rtol=1e-5, atol=1e-5)
