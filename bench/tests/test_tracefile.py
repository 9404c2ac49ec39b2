"""The trace reduction on a small synthesised trace (data/trace.pbtxt):
one TPU core's ops and the Python thread's annotations, times in ns."""
from pathlib import Path

import pytest

from bench import tracefile

TRACE = Path(__file__).parent / "data" / "trace.pbtxt"


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    return tracefile.reduce(ProfileData.from_text_proto(
        TRACE.read_text()).planes)


def test_window_busy_and_idle(summary):
    # Window 1000..21000 ns; ops cover 4000..9000, 12000..14000 and, cut
    # at the window's end, 20000..21000; the op before the window is out.
    assert summary.window_s == pytest.approx(20e-6)
    assert summary.busy_s == pytest.approx(8e-6)
    assert summary.idle_share == pytest.approx(0.6)
    assert summary.devices == 1


def test_kernels_are_custom_calls_by_name(summary):
    assert summary.kernel_s == pytest.approx(
        {"encode_pack": 2e-6, "am_search_packed": 5e-6})
    assert summary.kernel_count == {"encode_pack": 1, "am_search_packed": 2}
    # A pad whose operand is a custom-call is not a kernel.
    assert summary.op_s["pad.7 f32[896,1024]"] == pytest.approx(0.5e-6)


def test_idle_gaps_named_by_innermost_host_event(summary):
    assert summary.gaps == [
        ("bench.serve_batches", pytest.approx(6e-6)),
        ("bench.serve_batches", pytest.approx(3e-6)),
        ("np.asarray(jax.Array)", pytest.approx(3e-6))]
    bd = summary.breakdown()
    assert bd["device_ops"][0] == ["am_search_packed.1 s32[1024,1]",
                                   pytest.approx(5e-6)]
    assert len(bd["idle_gaps"]) == 3


@pytest.mark.parametrize("name,kernel", [
    ("%qail_update.3 = (f32[1024,1024]{1,0}, f32[1,128]{1,0}) "
     "custom-call(f32[256,1024]{1,0} %p)", "qail_update"),
    ("%am_shortlist = (s32[8,8]{1,0}) custom-call(u8[8,128]{1,0} %q)",
     "am_shortlist"),
    ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %custom-call.1)", None),
])
def test_kernel_name(name, kernel):
    assert tracefile.kernel_name(name) == kernel


def test_trace_without_window_is_refused():
    from jax.profiler import ProfileData
    text = TRACE.read_text().replace("bench.window", "other")
    with pytest.raises(ValueError):
        tracefile.reduce(ProfileData.from_text_proto(text).planes)
