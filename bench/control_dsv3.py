"""The controls of the DeepSeek-V3 front-end cell: the plain reference
put in the program's place, computed another way than the configuration
states, run through the whole harness at the cell's own size. Each has
to come out not correct.

    python3 bench/control_dsv3.py <workload> <control>[,<control>...] \
        <seconds> <seed>...

prints one JSON line per control and seed with ``correct`` and every
number compared beside its limit. The controls (``reference_dsv3.
CONTROLS``): ``fp8`` (every matmul operand rounded to float8 e4m3, the
nearest precision below the stated bfloat16), ``no_group_routing``,
``no_routed_scale`` and ``no_mscale``. The benchmark's own runs never
run it; ``bench/tests/test_dsv3_cell.py`` runs it at sizes a CPU holds.
"""
import hashlib
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

from bench import reference_dsv3, systems  # noqa: E402


class Served:
    """Serves the reference's ``variant`` in the artifact's row layout:
    class, packed query words, held slot counts, no dropped slot, the
    row's token count."""

    def __init__(self, system, variant):
        self.system, self.variant = system, variant

    def predict_features(self, tokens):
        cls, bits, counts = self.system.answers(np.asarray(tokens),
                                                variant=self.variant)
        words = np.packbits(bits, axis=-1, bitorder="little").view("<i4")
        n, s = np.shape(tokens)
        return jnp.asarray(np.concatenate(
            [cls[:, None], words, counts.reshape(n, -1),
             np.zeros((n, 1), np.int32), np.full((n, 1), s)],
            axis=1).astype(np.int32))

    predict = predict_features


# (configuration, seed, rows) -> the sound reference's answers: the check
# after each control's window computes the same ones, so they are kept.
_SOUND: dict = {}


def in_place(variant, patch=setattr):
    """Make ``systems.build`` hand out the reference's ``variant`` as the
    served artifact; ``patch`` is ``setattr`` or ``monkeypatch.setattr``."""
    real = systems.build

    def build(cfg, seed):
        s = real(cfg, seed)
        s.artifact = Served(s, variant)
        answers = s.answers

        def kept(tokens, variant=reference_dsv3.SOUND):
            if variant != reference_dsv3.SOUND:
                return answers(tokens, variant=variant)
            rows = np.ascontiguousarray(tokens)
            key = (json.dumps(cfg, sort_keys=True), seed,
                   hashlib.sha1(rows.tobytes()).hexdigest(), rows.shape)
            if key not in _SOUND:
                _SOUND[key] = answers(tokens)
            return _SOUND[key]
        s.answers = kept
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
    name, controls, seconds = argv[0], argv[1].split(","), float(argv[2])
    c = run.load_cell(name)
    devs = run.chips_or_exit(c["cell"]["chips"])
    real = systems.build
    for control in controls:
        systems.build = real
        in_place(reference_dsv3.CONTROLS[control])
        for seed in map(int, argv[3:]):
            t0 = time.perf_counter()
            line, _ = run.run_cell(c, seed, seconds, False, t0, devs)
            print(json.dumps(dict(
                workload=name, control=control, seed=seed,
                correct=line["correct"], attempted=line["attempted"],
                checks=line["checks"],
                seconds=time.perf_counter() - t0)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
