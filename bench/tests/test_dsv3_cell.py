"""The DeepSeek-V3 front-end cell: it loads by name, a small run of it on
the CPU is correct, each control comes out not correct, and the grouped
matmul is a named kernel the trace reduction finds.

The runs go through ``run.run_cell`` (the look for a chip skipped) at
widths a CPU holds, in float32, so that a sound run differs from the
reference in summation order only; every mechanism of the configuration
is kept (MLA with YaRN, group-limited routing over 16 experts in 4
groups, 4 held).
"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import control_dsv3, tracefile

from .test_harness import _run_py

CELL = "dsv3fe.docs2k"
SMALL = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=16, experts_held=4, experts_held_start=4, n_group=4,
    topk_group=2, num_experts_per_tok=4, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
    num_hidden_layers=3, first_k_dense_replace=1, dim=128, columns=64,
    classes=4, dtype="float32")


def small_cell():
    run = _run_py()
    c = run.load_cell(CELL)
    cfg = dict(c["cfg"], **SMALL)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    c["cfg"] = cfg
    c["traffic"] = dict(c["traffic"], row_tokens=64, pool_rows=8)
    return run, c


def run_small(seconds=0.3):
    run, c = small_cell()
    line, _ = run.run_cell(c, 2**31 + 23, seconds, False,
                           time.perf_counter(), jax.devices())
    return line


def test_cell_loads_by_name():
    run = _run_py()
    c = run.load_cell(CELL)
    assert c["cfg"]["system"] == "dsv3_frontend"
    assert c["traffic"]["mode"] == "prefill_bulk"
    assert set(c["limits"]["limits"]) == {
        "missing_answers", "dropped_slots", "query_bits_differ_ppm",
        "slot_counts_differ_ppm", "mismatch_ppm", "search_mismatch"}
    assert [m["name"] for m in c["end_to_end"]] == ["rows_per_s.flat",
                                                    "setup_s"]
    assert {m["name"] for m in c["per_layer"]} == {
        "mfu.dsv3fe", "idle_share.dsv3fe", "batcher_host_ms.dsv3fe"}
    for m in c["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_small_run_is_correct():
    line = run_small()
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0
    assert line["checks"]["dropped_slots"]["value"] == 0


@pytest.mark.parametrize("control", sorted(control_dsv3.reference_dsv3
                                           .CONTROLS))
def test_control_is_not_correct(control, monkeypatch):
    control_dsv3.in_place(control_dsv3.reference_dsv3.CONTROLS[control],
                          monkeypatch.setattr)
    line = run_small(seconds=0.1)
    assert not line["correct"], (control, line["checks"])


def test_moe_gmm_is_a_named_kernel_the_trace_finds():
    from repro.kernels.moe_gmm import moe_gmm
    lhs = jnp.zeros((256, 128), jnp.bfloat16)
    rhs = jnp.zeros((2, 128, 128), jnp.bfloat16)
    sizes = jnp.asarray([100, 50, 106], jnp.int32)
    jaxpr = jax.make_jaxpr(lambda a, b, g: moe_gmm(a, b, g, b))(
        lhs, rhs, sizes)
    names = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None:
                    walk(getattr(inner, "jaxpr", inner))
    walk(jaxpr.jaxpr)
    assert names == ["moe_gmm"]
    event = ("%moe_gmm.3 = bf16[4096,2048]{1,0} custom-call(s32[9]{0} "
             "%a, bf16[4096,7168]{1,0} %b), custom_call_target=\"tpu\"")
    assert tracefile.kernel_name(event) == "moe_gmm"


def test_search_fault_is_caught(monkeypatch):
    """A class other than the one the search gives for the query bits
    returned with it is caught exactly, while the classes against the
    reference's stay within their room (a quarter of the rows)."""
    from bench import systems
    real = systems.build

    class Shifted:
        """The artifact, with the first row of each batch given another
        class."""

        def __init__(self, artifact):
            self.artifact = artifact

        def predict_features(self, tokens):
            return self.artifact.predict_features(tokens).at[0, 0].add(1)

        predict = predict_features

    def build(cfg, seed):
        s = real(cfg, seed)
        s.artifact = Shifted(s.artifact)
        return s
    monkeypatch.setattr(systems, "build", build)
    line = run_small(seconds=0.1)
    assert line["checks"]["search_mismatch"]["value"] > 0, line["checks"]
    assert not line["correct"]
