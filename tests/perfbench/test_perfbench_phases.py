"""The per-phase readers (perfbench/phase_time.py and its metrics) on
synthetic planes with a given op → phase map, and the map the readers
build for themselves from a cell's configuration and traffic."""

import pytest

from perfbench import phase_time
from perfbench import trace_reduce as tr
from perfbench.spec import resolve

PHASE_READERS = ("act_share", "replay_share", "learner_share",
                 "grad_reduce_share")


def ev(name, start, dur, **stats):
    return (name, float(start), float(dur), stats)


def device(name, scale=1.0):
    # one iteration: the chunk's while holds every op; the learn
    # conditional holds the learner's ops
    s = scale
    return (name, [("XLA Ops", [
        ev("while.1", 0, 1000 * s),
        ev("%fusion.2 = f32[16]{0} fusion(%p), kind=kLoop", 0, 100 * s),
        ev("fusion.3", 100 * s, 50 * s),
        ev("fusion.4", 150 * s, 30 * s),
        ev("conditional.5", 200 * s, 600 * s),
        ev("sample_gather.6", 200 * s, 200 * s),
        ev("fusion.7", 400 * s, 250 * s),
        ev("%psum.8 = f32[] all-reduce(%x), channel_id=1", 650 * s, 40 * s),
        ev("fusion.9", 700 * s, 60 * s),
        ev("fusion.10", 800 * s, 20 * s),
        ev("copy.11", 820 * s, 80 * s),
    ])])


OP_PHASES = {"fusion.2": "act", "fusion.3": "insert_begin",
             "fusion.4": "flush", "conditional.5": "learn",
             "sample_gather.6": "sample", "fusion.7": "learner_update",
             "psum.8": "grad_reduce", "fusion.9": "write_back",
             "fusion.10": "insert_commit"}


def planes(chips=2):
    host = ("/host:CPU", [("python", [
        ev("run_chunk", 0, 30, step_num=0, length=1),
        ev("$executors.py:120 run_chunk", 0, 35),
        ev("run_chunk", 1000, 50, step_num=1, length=1),
        ev("wait", 1100, 900)])])
    return [device(f"/device:TPU:{i}", 1.0 + i) for i in range(chips)] + [
        host]


def ctx_for(op_phases=OP_PHASES, chips=2, iterations=2):
    return {"reduced": tr.reduce_planes(planes(chips)), "window_s": 1e-5,
            "iterations": iterations, "op_phases": op_phases}


def read(workload, ctx):
    cell = resolve(workload)
    return {name: cell.readers[name](ctx) for name in cell.readers
            if name in PHASE_READERS + ("dispatch_share",)}


def test_every_cell_reads_the_phases():
    for w in ("dqn_cartpole.ratio2.pallas", "ddpg_pendulum.ratio1.xla",
              "dqn_cartpole.ratio2.xla"):
        assert set(read(w, ctx_for())) == set(PHASE_READERS) - {
            "grad_reduce_share"} | {"dispatch_share"}
    w = "dqn_cartpole.ratio2.xla.4chip"
    assert set(read(w, ctx_for())) == set(PHASE_READERS) | {
        "dispatch_share"}


def test_readers_on_synthetic_planes():
    w = "dqn_cartpole.ratio2.xla.4chip"
    got = read(w, ctx_for())
    # each plane's busy self time is the while's 1000 (ns, × its scale)
    pct = lambda ns: 100.0 * ns / 1000
    assert got["act_share"] == pytest.approx(pct(100))
    assert got["replay_share"] == pytest.approx(pct(50 + 30 + 200 + 60 + 20))
    # the conditional's own time: 600 less the ops it holds (200+250+40+60)
    assert got["learner_share"] == pytest.approx(pct(250 + 50))
    assert got["grad_reduce_share"] == pytest.approx(pct(40))
    # two run_chunk spans of 30 and 50 ns in a 10 µs window; the python
    # frame is not one
    assert got["dispatch_share"] == pytest.approx(0.8)


def test_phases_and_unattributed_sum_to_busy_self_time(capsys):
    ctx = ctx_for()
    times = phase_time.phase_ns(ctx)
    reduced = ctx["reduced"]
    busy_self = sum(sum(d.op_ns.values())
                    for d in reduced.devices) / len(reduced.devices)
    assert sum(times.values()) == pytest.approx(busy_self, rel=1e-9)
    # the while's own time (1000 less the 880 of the ops it holds
    # directly) and the copy have no phase
    assert times[None] == pytest.approx(1.5 * (120 + 80))
    err = capsys.readouterr().err
    assert "unattributed" in err and "copy.11" in err


def test_a_reader_is_silent_where_its_phase_is_absent():
    no_reduce = {k: v for k, v in OP_PHASES.items() if v != "grad_reduce"}
    w = "dqn_cartpole.ratio2.xla.4chip"
    got = read(w, ctx_for(op_phases=no_reduce))
    assert got["grad_reduce_share"] is None
    assert got["act_share"] is not None
    # no map (a program that names no phases): every phase reader is
    # silent, the dispatch span still reads
    got = read(w, ctx_for(op_phases=None))
    assert all(got[n] is None for n in PHASE_READERS)
    assert got["dispatch_share"] is not None
    # no run_chunk span (a program without the executor's marker)
    ctx = ctx_for()
    ctx["reduced"].host_events[:] = [e for e in ctx["reduced"].host_events
                                     if e[0] != "run_chunk"]
    assert read(w, ctx)["dispatch_share"] is None


def test_without_op_phases_in_the_program_nothing_is_built(monkeypatch):
    from repro.runtime import executors

    monkeypatch.delattr(executors.Executor, "op_phases")
    ctx = ctx_for()
    del ctx["op_phases"]
    ctx.update(config=None, traffic=None)   # building would fail on these
    assert phase_time.op_phases(ctx) is None
    assert phase_time.share(ctx, ("act",)) is None


def test_the_rebuilt_map_is_the_probed_programs(tiny_cell):
    """The readers' own map (the cell's executor built again, its chunk
    compiled for the state's shapes) names the instructions of the
    program the probe ran."""
    from perfbench import cell as cell_mod

    cell = tiny_cell("dqn_cartpole.ratio2.xla")
    p = cell_mod.probe(cell, 2 ** 31 + 7)
    ran = p.built.executor.op_phases(p.state)
    ctx = {"config": cell.config, "traffic": cell.traffic}
    assert phase_time.op_phases(ctx) == ran
    assert {"act", "sample", "learner_update", "write_back"} <= set(
        ran.values())
