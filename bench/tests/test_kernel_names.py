"""Each kernel a roofline reader times is a ``pallas_call`` named as the
reader looks it up in the trace: the name is the kernel's own
(``pallas_call(name=...)``, which names the custom-call instruction),
not the jitted wrapper's it would otherwise take."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

METRICS = Path(__file__).resolve().parents[1] / "metrics"
READS = re.compile(r'roofline_pct\(ctx, "(\w+)"')
KERNELS = ["encode_pack", "am_search_packed", "am_shortlist",
           "am_search_sparse_gathered", "qail_update"]


def _u8(*shape):
    return jnp.zeros(shape, jnp.uint8)


def _calls():
    from repro.kernels import (am_search_packed, am_search_sparse,
                               am_shortlist, encode_fused, qail_update)
    f32 = jnp.zeros
    return {
        "encode_pack": lambda: encode_fused.encode_pack(
            f32((8, 16)), f32((16, 128))),
        "am_search_packed": lambda: am_search_packed.am_search_packed(
            _u8(8, 16), _u8(16, 128), n_dims=128),
        "am_shortlist": lambda: am_shortlist.am_shortlist(
            _u8(8, 16), _u8(16, 128), n_dims=128, s=2),
        "am_search_sparse_gathered":
            lambda: am_search_sparse.am_search_sparse_gathered(
                _u8(8, 16), _u8(8, 16, 128),
                jnp.zeros((8, 128), jnp.int32), n_dims=128, k=1),
        "qail_update": lambda: qail_update.qail_update(
            f32((8, 128)), f32((8, 128)), f32((128, 16)),
            jnp.zeros((16,), jnp.int32), jnp.zeros((8,), jnp.int32),
            jnp.ones((8,)), lr=0.1),
    }


def _pallas_names(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    yield from _pallas_names(getattr(inner, "jaxpr", inner))


def test_every_roofline_reader_has_a_kernel():
    read = {READS.search(p.read_text()).group(1)
            for p in METRICS.glob("*_roofline.py")}
    assert read == set(KERNELS) == set(_calls())


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_jaxpr_carries_the_read_name(kernel):
    jaxpr = jax.make_jaxpr(_calls()[kernel])().jaxpr
    assert list(_pallas_names(jaxpr)) == [kernel]
