"""The reduction from a profiler trace to device busy and idle time, on a
small trace recorded on one v5e chip (`benchmarks/testdata/`): three rounds
of `plan` (a 10 ms sleep), `execute` (a sort and a reduction, jitted) and
`between` (a 5 ms sleep). The numbers below are that trace's, worked out by
hand from its module events: 3 x (1.326 + 0.008) ms busy in 56.9 ms."""

import os

import pytest

from benchmarks import lib, tracereduce

TRACE = os.path.join(lib.HERE, "testdata", "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tracereduce.reduce_trace(TRACE)


def test_busy_share_of_the_recorded_trace(reduced):
    assert reduced["chips"] == 1 and reduced["statements"] == 3
    assert reduced["window_s"] == pytest.approx(0.0569336, rel=1e-5)
    assert reduced["busy_s"] == pytest.approx(0.00400243, rel=1e-5)
    idle_share = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle_share == pytest.approx(0.9297, abs=1e-4)


def test_top_operations_carry_their_program(reduced):
    ops = dict(reduced["device_ops"])
    assert len(reduced["device_ops"]) <= 10
    assert reduced["device_ops"][0][0] == "jit_step/sort.6"
    assert ops["jit_step/sort.6"] == pytest.approx(0.003344295, rel=1e-5)
    assert ops["jit_small/add_reduce_fusion"] == pytest.approx(2.3527e-5, rel=1e-4)
    # operations never overlap on one core: their sum is the busy time
    assert sum(ops.values()) <= reduced["busy_s"] * (1 + 1e-9)


def test_idle_time_goes_to_what_the_host_was_doing(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert list(gaps) == ["plan", "between", "execute"]
    assert gaps["plan"] == pytest.approx(0.0292199, rel=1e-4)
    assert gaps["between"] == pytest.approx(0.0156687, rel=1e-4)
    assert gaps["execute"] == pytest.approx(0.0080194, rel=1e-4)
    # the window is busy time, idle time under an annotation, and the rest
    assert sum(gaps.values()) + reduced["unannotated_s"] + reduced["busy_s"] \
        == pytest.approx(reduced["window_s"], rel=1e-2)


def test_a_trace_without_a_device_is_refused(tmp_path):
    with pytest.raises(Exception):
        tracereduce.reduce_trace(str(tmp_path / "missing.xplane.pb"))
