"""The control: the plain reference put in the program's place, computed
in a lower precision than the configuration states, run through the
whole harness at a cell's own size. It has to come out not correct.

    python3 bench/control.py <workload> <HIGH|DEFAULT> <seconds> <seed>...

prints one JSON line per seed with ``correct`` and every number compared
beside its limit. ``HIGH`` is three bf16 passes of the projection (the
nearest precision below the configurations' ``HIGHEST``), ``DEFAULT``
one pass. The benchmark's own runs never run it; ``bench/tests/
test_controls.py`` runs it at sizes a CPU holds.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import reference, systems  # noqa: E402

PRECISIONS = {"HIGH": jax.lax.Precision.HIGH,
              "DEFAULT": jax.lax.Precision.DEFAULT}


class Served:
    """Serves the reference's answers at ``precision``."""

    def __init__(self, system, precision):
        self.system, self.precision = system, precision

    def predict_features(self, x):
        return jnp.asarray(self.system.answers(np.asarray(x),
                                               precision=self.precision))

    predict = predict_features


class Trained:
    """Trains by the reference's QAIL at ``precision``."""

    def __init__(self, system, am_state, precision):
        self.system, self.am_state, self.precision = (system, am_state,
                                                      precision)

    def fit(self, key, x, y, *, epochs, **_):
        q = self.system.cfg["qail"]
        fp, binary, misses = reference.qail(
            self.am_state["fp"], self.am_state["centroid_class"], x, y,
            self.system.proj, epochs=epochs, batch=q["batch_size"],
            lr=q["lr"], precision=self.precision)
        state = dict(self.am_state, fp=fp, binary=binary)
        curve = [{"epoch": i + 1, "train_miss": float(m) / x.shape[0]}
                 for i, m in enumerate(np.asarray(misses))]
        return (Trained(self.system, state, self.precision),
                {"curve": curve})


def in_place(precision, patch=setattr):
    """Make ``systems.build`` hand out the reference at ``precision`` as
    the served artifact and the trained model; ``patch`` is ``setattr``
    or a test's ``monkeypatch.setattr``."""
    real = systems.build

    def build(cfg, seed):
        s = real(cfg, seed)
        s.artifact = Served(s, precision)
        if s.train is not None:
            x, y, fp0, owners, model = s.train
            s.train = (x, y, fp0, owners,
                       Trained(s, dict(model.am_state), precision))
        return s
    patch(systems, "build", build)


def main(argv):
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from repro import compile_cache
    from repro.obs import jaxmon
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jaxmon._installed = True  # as bench/run.py does, for the same reason
    name, precision, seconds = argv[0], argv[1], float(argv[2])
    in_place(PRECISIONS[precision])
    c = run.load_cell(name)
    devs = run.chips_or_exit(c["cell"]["chips"])
    for seed in map(int, argv[3:]):
        t0 = time.perf_counter()
        line, _ = run.run_cell(c, seed, seconds, False, t0, devs)
        print(json.dumps(dict(workload=name, precision=precision, seed=seed,
                              correct=line["correct"],
                              attempted=line["attempted"],
                              checks=line["checks"],
                              seconds=time.perf_counter() - t0)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
