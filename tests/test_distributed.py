"""Multi-device tests (8 fake CPU devices via subprocess).

Covers: ring collectives, shard_map MoE == local MoE, pjit'd train step
on a small mesh, the dry-run path end-to-end on a test mesh, and elastic
checkpoint re-shard (8 -> 4 devices).
"""
import pytest

from tests._multidev import check_multidev

pytestmark = pytest.mark.slow


def test_ring_collectives_match_allreduce():
    check_multidev("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compression import ring_reduce_scatter_int8, ring_all_gather, _BLOCK

mesh = jax.make_mesh((8,), ("pod",))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(8, 16, _BLOCK)).astype(np.float32))

def f(gl):
    red = ring_reduce_scatter_int8(gl[0], "pod")
    return ring_all_gather(red, "pod")[None]

out = jax.shard_map(f, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"))(g)
want = np.asarray(g.sum(axis=0))
got = np.asarray(out)[3]
rel = np.abs(got - want).max() / np.abs(want).max()
assert rel < 0.05, rel
# all members agree exactly
for i in range(8):
    np.testing.assert_array_equal(np.asarray(out)[i], np.asarray(out)[0])
print("OK")
""")


def test_sharded_moe_matches_local():
    check_multidev("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import layers as L
from repro.models.config import FfnSpec
from repro.models.sharding import ShardingRules, use_rules

spec = FfnSpec(kind="moe", d_ff=64, n_experts=8, n_shared=1, top_k=2,
               d_ff_expert=32, router="softmax", capacity_factor=8.0)
p, _ = L.init_moe_ffn(jax.random.key(0), 64, spec, jnp.float32)
x = jax.random.normal(jax.random.key(1), (4, 16, 64))

y_local, aux_local = L._moe_ffn_local(p, spec, x)

mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(mesh=mesh)
with mesh, use_rules(rules):
    y_sh, aux_sh = jax.jit(lambda pp, xx: L._moe_ffn_sharded(
        pp, spec, xx, rules))(p, x)

# Same routing, same experts -> same outputs (capacity_factor is large
# enough that neither path drops tokens).
np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_sh),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(aux_local["expert_counts"]),
                           np.asarray(aux_sh["expert_counts"]))
print("OK")
""")


def test_pjit_train_step_runs_and_matches_single_device():
    check_multidev("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.distributed.steps import make_train_step
from repro.models import transformer as T
from repro.models.sharding import ShardingRules, param_sharding_tree
from repro.optim import AdamWConfig, ScheduleConfig, make_schedule, adamw_init

cfg = get_smoke_config("qwen1.5-32b")
params, axes = T.init_params(jax.random.key(0), cfg)
opt_cfg = AdamWConfig(lr=1e-3)
opt = adamw_init(params, opt_cfg)
sched = make_schedule(ScheduleConfig(warmup_steps=1, total_steps=10))
toks = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
batch = {"tokens": toks, "targets": toks}

# Single-device reference.
step1 = jax.jit(make_train_step(cfg, opt_cfg, sched))
p1, o1, m1 = step1(params, opt, batch, jnp.asarray(0, jnp.int32))

# 2x4 mesh pjit.
mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(mesh=mesh, fsdp=True)
p_sh = param_sharding_tree(axes, rules, params)
with mesh:
    step8 = jax.jit(make_train_step(cfg, opt_cfg, sched, rules),
                    in_shardings=(p_sh, {"m": p_sh, "v": p_sh,
                                         "step": NamedSharding(mesh, P())},
                                  {"tokens": NamedSharding(mesh, P("data", None)),
                                   "targets": NamedSharding(mesh, P("data", None))},
                                  NamedSharding(mesh, P())),
                    out_shardings=(p_sh, None, None))
    p8, o8, m8 = step8(params, opt, batch, jnp.asarray(0, jnp.int32))

assert abs(float(m1["loss"]) - float(m8["loss"])) < 1e-3, (m1["loss"], m8["loss"])
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-3)
print("OK")
""")


def test_dryrun_cell_on_test_mesh():
    """The full dry-run path (abstract state, shardings, lower, compile,
    roofline extraction) on a 2x4 mesh with a smoke config."""
    check_multidev("""
import dataclasses, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.distributed import hlo_cost
from repro.distributed.steps import abstract_train_state, make_train_step
from repro.models.sharding import ShardingRules, param_sharding_tree
from repro.optim import AdamWConfig, ScheduleConfig, make_schedule

cfg = get_smoke_config("deepseek-v2-lite-16b")
cfg = dataclasses.replace(cfg, remat=True)
mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(mesh=mesh, fsdp=True)
opt_cfg = AdamWConfig()
params_sds, opt_sds, axes = abstract_train_state(cfg, opt_cfg)
p_sh = param_sharding_tree(axes, rules, params_sds)
batch_sds = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "targets": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
b_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch_sds}
sched = make_schedule(ScheduleConfig())
with mesh:
    step = jax.jit(make_train_step(cfg, opt_cfg, sched, rules),
                   in_shardings=(p_sh, {"m": p_sh, "v": p_sh,
                                        "step": NamedSharding(mesh, P())},
                                 b_sh, NamedSharding(mesh, P())),
                   out_shardings=(p_sh, None, None))
    lowered = step.lower(params_sds, opt_sds, batch_sds,
                         jax.ShapeDtypeStruct((), jnp.int32))
    compiled = lowered.compile()
ma = compiled.memory_analysis()
assert ma.argument_size_in_bytes > 0
tot = hlo_cost.analyze(compiled.as_text(), 8)
assert tot.flops > 0
assert tot.wire_bytes > 0  # sharded model must communicate
print("OK", tot.flops, tot.wire_bytes)
""")


def test_elastic_checkpoint_reshard():
    """Save on an '8-chip' mesh, restore onto a '4-chip' mesh."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        check_multidev(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointConfig, CheckpointManager

mesh = jax.make_mesh((8,), ("model",))
w = jnp.arange(64.0).reshape(8, 8)
w = jax.device_put(w, NamedSharding(mesh, P("model", None)))
mgr = CheckpointManager(CheckpointConfig({d!r}))
mgr.save(1, {{"w": w}})
print("SAVED")
""", n_devices=8)
        check_multidev(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointConfig, CheckpointManager

mesh = jax.make_mesh((4,), ("model",))
mgr = CheckpointManager(CheckpointConfig({d!r}))
step, tree, _ = mgr.restore({{"w": jnp.zeros((8, 8))}})
assert step == 1
w = jax.device_put(tree["w"], NamedSharding(mesh, P("model", None)))
np.testing.assert_array_equal(np.asarray(w),
                              np.arange(64.0).reshape(8, 8))
print("RESHARDED OK")
""", n_devices=4)


def test_distributed_memhd_qail_matches_single_device():
    check_multidev("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import EncoderConfig, MemhdConfig, MemhdModel, qail
from repro.core.distributed import fit_distributed
from repro.data import load_dataset

ds = load_dataset("mnist", train_per_class=40, test_per_class=10)
enc = EncoderConfig(kind="projection", features=ds.features, dim=128)
amc = MemhdConfig(dim=128, columns=32, classes=ds.classes, epochs=2,
                  kmeans_iters=5, lr=0.02)
m = MemhdModel.create(jax.random.key(0), enc, amc)
m, _ = m.initialize_am(jax.random.key(1), ds.train_x, ds.train_y)

# Single-device reference: batched QAIL with one full-dataset batch.
h = m.encode(ds.train_x); q = jnp.where(h >= 0, 1.0, -1.0)
state = m.am_state
for _ in range(2):
    state, _ = qail.qail_batch_update(state, amc, h, q, ds.train_y)
    state = qail.qail_finalize_epoch(state, amc)

mesh = jax.make_mesh((2, 4), ("data", "model"))
m2 = fit_distributed(mesh, m, ds.train_x, ds.train_y, epochs=2)

# The distributed epoch syncs Eq.-6 deltas in bf16 (EXPERIMENTS §Perf Q2),
# so agreement is to bf16-delta precision, not bit-exact.
# (Eq.-4/5 argmax targets may flip for borderline samples after the
# first epoch's rounding, so the float trajectories diverge slightly
# beyond pure rounding — and by a run-dependent amount, since CPU
# scatter-add ordering is nondeterministic. The float check is a loose
# sanity bound; the assertion with teeth is on the binary AM — the
# artifact that actually deploys.)
fp_a, fp_b = np.asarray(state["fp"]), np.asarray(m2.am_state["fp"])
scale = np.abs(fp_a).max()
assert np.abs(fp_a - fp_b).max() < 0.15 * scale, \
    np.abs(fp_a - fp_b).max() / scale
bin_agree = (np.asarray(state["binary"])
             == np.asarray(m2.am_state["binary"])).mean()
assert bin_agree > 0.99, bin_agree
print("OK distributed QAIL == single-device QAIL (bf16 sync tolerance)")
""")


def test_memhd_fit_sharded_matches_single_device():
    """fit_sharded (shard_map scan epochs, bf16 delta wire) vs plain
    fit on one device: same init, same schedule — the deployed binary
    AM must agree almost everywhere and accuracy must match."""
    check_multidev("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import EncoderConfig, MemhdConfig, MemhdModel
from repro.data import load_dataset

ds = load_dataset("mnist", train_per_class=40, test_per_class=10)
enc = EncoderConfig(kind="projection", features=ds.features, dim=128)
amc = MemhdConfig(dim=128, columns=32, classes=ds.classes, epochs=3,
                  kmeans_iters=5, lr=0.02, batch_size=128)
m = MemhdModel.create(jax.random.key(0), enc, amc)
m_fit, _ = m.fit(jax.random.key(1), ds.train_x, ds.train_y)

mesh = jax.make_mesh((8,), ("data",))
m_sh, hist = m.fit_sharded(jax.random.key(1), ds.train_x, ds.train_y,
                           mesh=mesh)
agree = (np.asarray(m_sh.am_state["binary"])
         == np.asarray(m_fit.am_state["binary"])).mean()
assert agree > 0.95, agree
acc_f = m_fit.score(ds.test_x, ds.test_y)
acc_s = m_sh.score(ds.test_x, ds.test_y)
assert abs(acc_f - acc_s) < 0.08, (acc_f, acc_s)
assert len(hist["curve"]) == 3
print("OK fit_sharded binary agreement", agree)
""")


def test_memhd_dryrun_epoch_on_test_mesh():
    check_multidev("""
import jax
from repro.core.distributed import dryrun_epoch
mesh = jax.make_mesh((2, 4), ("data", "model"))
rep = dryrun_epoch(mesh, n_samples=512, dim=256, columns=256)
r = rep["roofline"]
assert r["flops_per_dev"] > 0 and r["useful_flops_ratio"] > 0.2, r
print("OK", r["dominant"], r["useful_flops_ratio"])
""")


def test_seq_parallel_flash_decode_matches_reference():
    check_multidev("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import layers as L
from repro.models.config import AttnSpec
from repro.models.sharding import ShardingRules, use_rules

spec = AttnSpec(kind="gqa", n_heads=8, n_kv_heads=2, head_dim=16)
d = 64
p, _ = L.init_gqa(jax.random.key(0), d, spec, jnp.float32)
B, S = 4, 64
cache = L.init_gqa_cache(spec, B, S, jnp.float32)
xs = jax.random.normal(jax.random.key(1), (B, S, d))

c_ref = cache
for t in range(8):
    y_ref, c_ref = L.gqa_decode(p, spec, xs[:, t:t+1], c_ref)

mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(mesh=mesh, shard_seq=True)
c_sp = cache
with mesh, use_rules(rules):
    f = jax.jit(lambda pp, xx, cc: L.gqa_decode(pp, spec, xx, cc,
                                                seq_parallel=True))
    for t in range(8):
        y_sp, c_sp = f(p, xs[:, t:t+1], c_sp)

np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sp),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(c_ref["k"]), np.asarray(c_sp["k"]),
                           rtol=1e-5, atol=1e-5)
print("OK")
""")
