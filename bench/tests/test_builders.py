"""A configuration's system is built by ``bench/builders/<system>.py``,
found by name, so that a new configuration comes as new files only."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import systems

from .test_reference import SMALL_FLAT, SMALL_HIER

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("name", ["no_such_system", "../systems",
                                  "__init__", "sampled_flat.build"])
def test_unknown_system_names_known_builders(name):
    with pytest.raises(ValueError, match="sampled_flat") as err:
        systems.build(dict(SMALL_FLAT, system=name), seed=1)
    assert "planted_hierarchical" in str(err.value)


def _digest(a):
    a = np.asarray(a)
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


# Made by ``bench/systems.py`` before its builders moved into files of
# their own, at seed 2**31 + 7: the move changed no array.
PINNED = {
    "flat": {"proj": "dbc107f622a18af9", "am": "9c776ff7b38a4561",
             "rows": "9e81ad6240215783", "train_am": "3db6bb8cb1ef8df9"},
    "hier": {"proj": "a2300b1fb3ffb53e", "am": "358fd511d78aeba0",
             "rows": "c010f21dee1acbdd"},
}


@pytest.mark.parametrize("cfg,pinned", [(SMALL_FLAT, PINNED["flat"]),
                                        (SMALL_HIER, PINNED["hier"])],
                         ids=["flat", "hierarchical"])
def test_builders_make_pinned_arrays(cfg, pinned):
    s = systems.build(cfg, seed=2**31 + 7)
    got = {"proj": _digest(s.proj), "am": _digest(s.model.am_state["fp"]),
           "rows": _digest(s.rows(96))}
    if s.train is not None:
        got["train_am"] = _digest(s.train[2])
    assert got == pinned


# A toy configuration, added as new files only.
TOY_FILES = {
    "bench/configs/toy-flat.json": dict(SMALL_FLAT, system="toy_flat",
                                        reduced=[]),
    "bench/builders/toy_flat.py": (
        '"""sampled_flat under a name of its own."""\n'
        "from bench.builders import sampled_flat\n\n\n"
        "def build(cfg, seed):\n"
        "    return sampled_flat.build(cfg, seed)\n"),
    "bench/traffic/toy_bulk.json": {
        "mode": "bulk", "request_rows": 16, "requests_per_call": 32,
        "max_batch": 128, "depth": 2, "pool_rows": 2048},
    "bench/limits/toy.bulk.json": {
        "limits": {"missing_answers": 0, "mismatch_ppm": 0}},
    "bench/metrics/toy_krows_per_s.py": (
        '"""Thousands of rows a second."""\n\n\n'
        "def read(ctx):\n"
        '    return ctx["end_to_end"]["rows_per_s"] / 1000.0\n'),
}

# Loads the copy's toy cell and runs it once, on the CPU, with the
# look for a chip skipped.
TOY_RUN = """
import importlib.util, json, sys, time
root = sys.argv[1]
sys.path.insert(0, root)
spec = importlib.util.spec_from_file_location("bench_run",
                                              root + "/bench/run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
import jax
from bench import systems
c = run.load_cell("toy.bulk")
line, _ = run.run_cell(c, 2**31 + 7, 0.3, False, time.perf_counter(),
                       jax.devices())
builder = sys.modules["bench.builders.toy_flat"]
print(json.dumps(dict(
    line=line, system=c["cfg"]["system"],
    files=[systems.__file__, builder.__file__],
    end_to_end=[m["name"] for m in c["end_to_end"]],
    per_layer=[m["name"] for m in c["per_layer"]],
    reader=run.reader("toy_krows_per_s")(
        {"end_to_end": {"rows_per_s": 2500.0}}))))
"""


def _tree(root):
    return {p.relative_to(root).as_posix(): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_configuration_as_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree(tmp_path)

    for rel, body in TOY_FILES.items():
        assert not (tmp_path / rel).exists()
        text = body if isinstance(body, str) else json.dumps(body)
        (tmp_path / rel).write_text(text)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "toy-flat", "source": "https://arxiv.org/abs/2502.07834",
        "file": "bench/configs/toy-flat.json", "reduced": [],
        "why": "a test's configuration"})
    spec["workloads"].append({
        "name": "toy.bulk", "config": "toy-flat", "traffic": "toy_bulk",
        "chips": 1, "why": "a test's cell"})
    spec["per_layer"].append({
        "name": "toy_krows_per_s", "unit": "krows/s", "better": "higher",
        "source": "host_clock", "layer": "whole step",
        "moves": "rows_per_s.flat", "workloads": ["toy.bulk"]})
    flat = next(m for m in spec["end_to_end"]
                if m["name"] == "rows_per_s.flat")
    flat["workloads"].append("toy.bulk")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _tree(tmp_path)
    edited = {rel for rel, data in before.items() if after[rel] != data}
    assert edited == {"BENCHMARK.json"}
    assert set(after) - set(before) == set(TOY_FILES)

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", TOY_RUN, str(tmp_path)],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["system"] == "toy_flat"
    for f in got["files"]:
        assert Path(f).resolve().is_relative_to(tmp_path.resolve())
    assert got["end_to_end"] == ["rows_per_s.flat", "setup_s"]
    assert got["per_layer"] == ["toy_krows_per_s"]
    assert got["reader"] == 2.5
    line = got["line"]
    assert line["correct"] and line["attempted"] > 0, line
    assert set(line["metrics"]) == {"rows_per_s.flat", "setup_s"}
