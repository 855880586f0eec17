"""The trace reduction: union of device-op intervals, kernel and
collective time, idle gaps, on synthetic planes."""

import pytest

from perfbench import trace_reduce as tr


def ev(name, start, dur, **stats):
    return (name, float(start), float(dur), stats)


def test_union_of_overlapping_intervals():
    assert tr.union_intervals([(0, 10), (5, 12), (20, 30), (30, 31),
                               (25, 26)]) == [(0, 12), (20, 31)]
    assert tr.union_intervals([]) == []


def test_synthetic_planes():
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [ev("jit_chunk", 0, 1000)]),
            ("XLA Ops", [ev("fusion.1", 0, 100), ev("fusion.2", 50, 100),
                         ev("sample_gather", 300, 400),
                         ev("custom-call.7", 800, 50,
                            long_name="sumtree_update(...)"),
                         ev("all-reduce.3", 900, 20)]),
        ]),
        ("/device:TPU:1", [
            ("XLA Ops", [ev("all-reduce-start.1", 0, 30),
                         ev("fusion.1", 10, 10)]),
        ]),
        ("/host:CPU", [("python", [ev("run_chunk", 120, 150),
                                   ev("wait", 700, 100),
                                   ev("outer", 0, 2000)])]),
    ]
    red = tr.reduce_planes(planes)
    d0, d1 = red.devices
    # busy: [0,150) + [300,700) + [800,850) + [900,920) = 620
    assert d0.busy_ns == 620
    assert d1.busy_ns == 30
    assert red.busy_s_mean == pytest.approx(325e-9)
    assert d0.kernel_ns == {"sample_gather": 400, "sumtree_update": 50}
    assert red.kernel_calls("sample_gather") == 1
    assert d0.collective_ns == {"all-reduce": 20}
    assert red.collective_s() == pytest.approx(50e-9)
    # gaps longest first: [150,300) 150, [700,800) 100, [850,900) 50
    assert d0.gaps == [(150, 300), (700, 800), (850, 900)]
    bd = tr.breakdown(red)
    assert bd["idle_gaps"][0] == ["run_chunk", pytest.approx(150e-9)]
    assert bd["idle_gaps"][1] == ["wait", pytest.approx(100e-9)]
    assert bd["idle_gaps"][2] == ["outer", pytest.approx(50e-9)]
    assert bd["device_ops"][0] == ["sample_gather", pytest.approx(200e-9)]


def test_a_plane_without_ops_is_not_a_device():
    red = tr.reduce_planes([("/device:TPU:0", [("Steps", [ev("s", 0, 5)])])])
    assert red.devices == []




def test_ops_named_by_their_whole_hlo_instruction():
    """A TPU trace may name an op by its whole instruction; only the
    instruction's own name counts, not an operand that names a kernel."""
    gather = ("%sample_gather.23 = (s32[128,1]{1,0:T(8,128)S(1)}, "
              "f32[128,4]{1,0:T(8,128)S(1)}) custom-call(f32[128,1]{1,0} "
              "%copy-done.14), custom_call_target=\"tpu_custom_call\"")
    consumer = ("%get-tuple-element.5 = f32[128,4]{1,0} "
                "get-tuple-element(%sample_gather.23), index=1")
    reduce = ("%psum.7 = f32[256]{0:T(256)S(1)} all-reduce(%fusion), "
              "channel_id=1, replica_groups={{0,1,2,3}}")
    pmax = "pmax.2"
    planes = [("/device:TPU:0", [("XLA Ops", [
        ev(gather, 0, 700), ev(gather, 1000, 700),
        ev(consumer, 1700, 10, long_name=consumer),
        ev(reduce, 2000, 40),
        ev(pmax, 2100, 5, long_name="%pmax.2 = f32[]{:T(128)} "
           "all-reduce(%reduce_max.9), channel_id=2")])])]
    red = tr.reduce_planes(planes)
    (d,) = red.devices
    assert d.kernel_ns == {"sample_gather": 1400}
    assert red.kernel_calls("sample_gather") == 2
    assert d.collective_ns == {"all-reduce": 45}
    assert d.op_ns["sample_gather.23"] == 1400
    assert red.busy_s_total() == pytest.approx(1455e-9)
    assert tr.breakdown(red)["device_ops"][0] == [
        "sample_gather.23", pytest.approx(1400e-9)]


def load_fixture(name):
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "fixtures", name)
    with open(path) as f:
        fx = json.load(f)
    return fx, [(pn, [(ln, [tuple(e) for e in evs]) for ln, evs in lines])
                for pn, lines in fx["planes"]]


def test_recorded_tpu_trace_of_the_pallas_cell():
    """3 ms of a TPU v5e trace of dqn_cartpole.ratio2.pallas
    (tests/perfbench/record_fixture.py): the chunk's ``while`` and the
    learn's ``conditional`` sit on the ops line with the ops they run,
    and every op is named by its whole HLO instruction."""
    fx, planes = load_fixture("trace_pallas_v5e.json")
    assert fx["device_kind"] == "TPU v5 lite"
    red = tr.reduce_planes(planes)
    (d,) = red.devices
    # read by hand: the while spans every other op
    assert d.busy_ns == 3207997741
    assert d.kernel_ns == {"sample_gather": 5707549}
    assert red.kernel_calls("sample_gather") == 1
    assert d.collective_ns == {}
    # self times: the conditional holds copy.210, four copy-dones and
    # the kernel; the while holds the conditional
    assert d.op_ns["conditional.17"] == 50016018 - 830211 - 10 - 5707549
    assert d.op_ns["while.45"] == 3207997741 - 50016018
    assert d.op_ns["sample_gather.16"] == 5707549
    assert tr.breakdown(red)["device_ops"][1] == [
        "conditional.17", pytest.approx(0.043478248)]
    assert tr.host_activity(red, 0.0) == "$api.py:3108 try_to_block"
