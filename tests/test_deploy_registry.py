"""Unified deployment-backend subsystem: registry dispatch, legacy-shim
compatibility, round-trip predict parity for every registered backend,
and pytree flatten/unflatten stability of every artifact under jax.jit."""
import jax
import numpy as np
import pytest

from repro.deploy import (DeployedArtifact, available_backends, deploy,
                          get_backend, register_backend)
from repro.deploy.base import pytree_artifact

# The frontend backend serves token rows through a frozen LM in front of
# the packed AM, not feature rows; tests/test_dsv3_frontend.py checks it.
BACKENDS = sorted(t for t in available_backends() if t != "frontend")
# The multibit backend reads out against the QUANTIZED float shadow,
# not the binary AM — bit-exact parity with model.predict is the wrong
# contract for it (its oracle parity lives in TestMultibitBackend).
BINARY_PARITY_BACKENDS = [t for t in BACKENDS if t != "multibit"]


@pytest.fixture(scope="module")
def trained(small_hdc_data):
    """A small trained model (shared across every backend check)."""
    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    ds = small_hdc_data
    enc = EncoderConfig(kind="projection", features=ds.features, dim=128)
    amc = MemhdConfig(dim=128, columns=32, classes=ds.classes,
                      epochs=1, kmeans_iters=3)
    m = MemhdModel.create(jax.random.key(0), enc, amc)
    m, _ = m.fit(jax.random.key(1), ds.train_x, ds.train_y)
    return ds, m


class TestRegistry:
    def test_builtin_backends_registered(self):
        assert {"packed", "unpacked", "imc", "hierarchical",
                "multibit"} <= set(BACKENDS)
        assert "frontend" in available_backends()

    def test_unknown_target_error_names_backends(self, trained):
        _, m = trained
        with pytest.raises(ValueError, match="unknown deploy target"):
            m.deploy(target="bogus")
        with pytest.raises(ValueError) as ei:
            get_backend("bogus")
        # The error enumerates what IS registered.
        for name in ("packed", "unpacked", "imc"):
            assert name in str(ei.value)

    def test_registry_function_dispatch(self, trained):
        _, m = trained
        dep = deploy(m, "packed")
        assert dep.backend == "packed"
        assert get_backend("imc")(m).backend == "imc"

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_backend("packed")(lambda model: None)
        # Re-registering the SAME factory (module reload) is a no-op.
        factory = get_backend("packed")
        assert register_backend("packed")(factory) is factory

    def test_third_party_backend_plugs_in(self, trained):
        _, m = trained

        @register_backend("_test_echo")
        def _echo(model, *, tag="x"):
            return (model, tag)

        try:
            got_m, tag = m.deploy(target="_test_echo", tag="y")
            assert got_m is m and tag == "y"
        finally:
            from repro.deploy import registry
            registry._BACKENDS.pop("_test_echo")


class TestLegacyShims:
    """Old deploy() call forms and import paths keep working."""

    def test_import_paths(self):
        from repro.core import DeployedMemhd as d1
        from repro.core.memhd import DeployedMemhd as d2
        from repro.deploy.digital import DeployedMemhd as d3
        assert d1 is d2 is d3
        from repro.imcsim import ImcDeployedMemhd, deploy_imc  # noqa: F401

    def test_packed_kwarg(self, trained):
        _, m = trained
        assert m.deploy().backend == "packed"
        assert m.deploy(packed=True).backend == "packed"
        assert m.deploy(packed=False).backend == "unpacked"
        assert m.deploy(target="digital", packed=False).backend == \
            "unpacked"
        assert m.deploy(packed=True, mode="unpack").mode == "unpack"

    def test_imc_target_with_sim(self, trained):
        from repro.core import ImcSimConfig
        from repro.imcsim import ImcDeployedMemhd
        _, m = trained
        dep = m.deploy(target="imc", sim=ImcSimConfig(seed=3))
        assert isinstance(dep, ImcDeployedMemhd)
        assert dep.sim.seed == 3

    def test_sim_rejected_for_digital(self, trained):
        from repro.core import ImcSimConfig
        _, m = trained
        with pytest.raises(ValueError, match="target='imc'"):
            m.deploy(packed=True, sim=ImcSimConfig())

    def test_packed_kwarg_rejected_with_registry_target(self, trained):
        _, m = trained
        with pytest.raises(ValueError, match="legacy"):
            m.deploy(target="packed", packed=True)


class TestBackendParity:
    """deploy(target=t).predict == model.predict for every backend.

    (The imc backend's default sim is ideal — the fidelity-parity
    contract of tests/test_imcsim.py.)
    """

    @pytest.mark.parametrize("target", BINARY_PARITY_BACKENDS)
    def test_predict_roundtrip(self, trained, target):
        ds, m = trained
        dep = m.deploy(target=target)
        assert isinstance(dep, DeployedArtifact)
        np.testing.assert_array_equal(
            np.asarray(dep.predict(ds.test_x[:48])),
            np.asarray(m.predict(ds.test_x[:48])))
        # predict_features serves the same answers (fused or staged).
        np.testing.assert_array_equal(
            np.asarray(dep.predict_features(ds.test_x[:48])),
            np.asarray(m.predict(ds.test_x[:48])))

    @pytest.mark.parametrize("target", BINARY_PARITY_BACKENDS)
    def test_score_matches_model(self, trained, target):
        ds, m = trained
        dep = m.deploy(target=target)
        assert dep.score(ds.test_x, ds.test_y) == \
            m.score(ds.test_x, ds.test_y)

    @pytest.mark.parametrize("target", BACKENDS)
    def test_score_queries_matches_score(self, trained, target):
        ds, m = trained
        dep = m.deploy(target=target)
        q = m.encode_query(ds.test_x)
        assert dep.score_queries(q, ds.test_y) == \
            dep.score(ds.test_x, ds.test_y)

    @pytest.mark.parametrize("target", BACKENDS)
    def test_protocol_surface(self, trained, target):
        _, m = trained
        dep = m.deploy(target=target)
        assert dep.backend == target
        assert isinstance(dep.serving_mode, str)
        assert dep.resident_bytes > 0
        assert dep.resident_am_bytes == dep.resident_bytes
        assert dep.am_memory_ratio > 0
        assert dep.imc_cost().total_cycles >= 1


class TestMultibitBackend:
    """Bit-sliced multi-bit artifact: oracle parity, Table-I accounting
    at multi-level cells, refresh semantics, and sim validation."""

    @pytest.mark.parametrize("bits", [2, 4])
    def test_oracle_parity(self, trained, bits):
        from repro.core import am as am_lib
        ds, m = trained
        dep = m.deploy(target="multibit", cell_bits=bits)
        q = m.encode_query(ds.test_x[:32])
        np.testing.assert_array_equal(
            np.asarray(dep.predict_query(q)),
            np.asarray(am_lib.multibit_predict(
                dep.am_planes_t, dep.centroid_class, q, bits)))
        # search_query sims are the code-domain sims dequantized.
        from repro.kernels import ref
        _, sims = dep.search_query(q)
        _, code_sims = ref.am_search_multibit(q, dep.am_planes_t,
                                              cell_bits=bits)
        np.testing.assert_allclose(
            np.asarray(sims),
            np.asarray(code_sims) * float(dep.am_scale), rtol=1e-6)

    @pytest.mark.parametrize("bits", [2, 4])
    def test_memory_bits_table1(self, trained, bits):
        _, m = trained
        dep = m.deploy(target="multibit", cell_bits=bits)
        d, c = m.am_cfg.dim, m.am_cfg.columns
        assert m.am_cfg.am_memory_bits_at(bits) == c * d * bits
        assert dep.memory_bits == m.enc_cfg.memory_bits + c * d * bits
        # Plane residence: bits planes of ceil(D/8) bytes per column.
        plane_bytes = bits * (-(-d // 8)) * c
        assert dep.am_planes_t.size == plane_bytes
        assert dep.resident_bytes >= plane_bytes
        # vs the 1-bit point the packing is exactly `bits` planes.
        assert m.am_cfg.am_memory_bits_at(bits) == \
            bits * m.am_cfg.am_memory_bits

    def test_refresh_keeps_signature_and_opts(self, trained):
        _, m = trained
        dep = m.deploy(target="multibit", cell_bits=2)
        fresh = dep.refresh(m)
        assert fresh is not dep
        assert fresh.cell_bits == 2 and fresh.backend == "multibit"
        assert fresh.swap_signature == dep.swap_signature
        np.testing.assert_array_equal(np.asarray(fresh.am_planes_t),
                                      np.asarray(dep.am_planes_t))

    def test_rejects_bad_cell_bits(self, trained):
        _, m = trained
        with pytest.raises(ValueError, match="packed"):
            m.deploy(target="multibit", cell_bits=1)
        with pytest.raises(ValueError, match="outside"):
            m.deploy(target="multibit", cell_bits=9)

    def test_rejects_storage_perturbation_sims(self, trained):
        from repro.core import ImcSimConfig
        _, m = trained
        for bad in (ImcSimConfig(noise_sigma=0.5),
                    ImcSimConfig(fault_p0=0.01),
                    ImcSimConfig(fault_p1=0.01)):
            with pytest.raises(ValueError, match="1-bit storage"):
                m.deploy(target="multibit", cell_bits=4, sim=bad)

    def test_drift_sim_attaches_offsets(self, trained):
        from repro.core import ImcSimConfig
        _, m = trained
        dep = m.deploy(target="multibit", cell_bits=4,
                       sim=ImcSimConfig(drift_sigma=0.2, seed=5))
        gd = -(-m.am_cfg.dim // dep.sim.arr.rows)
        gc = -(-m.am_cfg.columns // dep.sim.arr.cols)
        assert dep.tile_offsets.shape == (gd, gc)
        # Same seed refreshes onto the same simulated readout.
        np.testing.assert_array_equal(
            np.asarray(dep.refresh(m).tile_offsets),
            np.asarray(dep.tile_offsets))


class TestPytreeStability:
    """Artifacts are pytrees: flatten/unflatten and jit round-trips."""

    @pytest.mark.parametrize("target", BACKENDS)
    def test_flatten_unflatten_roundtrip(self, trained, target):
        ds, m = trained
        dep = m.deploy(target=target)
        leaves, treedef = jax.tree_util.tree_flatten(dep)
        rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
        assert type(rebuilt) is type(dep)
        assert rebuilt.am_cfg == dep.am_cfg
        np.testing.assert_array_equal(
            np.asarray(rebuilt.predict(ds.test_x[:16])),
            np.asarray(dep.predict(ds.test_x[:16])))

    @pytest.mark.parametrize("target", BACKENDS)
    def test_artifact_flows_through_jit(self, trained, target):
        ds, m = trained
        dep = m.deploy(target=target)
        q = m.encode_query(ds.test_x[:24])

        f = jax.jit(lambda art, qq: art.predict_query(qq))
        want = np.asarray(dep.predict_query(q))
        np.testing.assert_array_equal(np.asarray(f(dep, q)), want)
        # A flatten/unflatten round-trip hits the same jit cache entry
        # (identical treedef + aux), i.e. the pytree is jit-stable.
        leaves, treedef = jax.tree_util.tree_flatten(dep)
        rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
        np.testing.assert_array_equal(np.asarray(f(rebuilt, q)), want)
        assert f._cache_size() == 1

    def test_artifact_field_declarations_checked(self):
        import dataclasses as dc

        with pytest.raises(TypeError, match="_leaf_fields"):
            @pytree_artifact
            @dc.dataclass
            class Bad(DeployedArtifact):  # noqa: F841
                x: int
                _leaf_fields = ()
                _static_fields = ()