"""The step's phase scopes (runtime/phases.py): the op → phase map of
hand-written and compiled chunks, the executor's ``run_chunk`` span,
and the scopes' promise to change no op."""

import contextlib
import functools
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.runtime.phases import PHASES, op_phases, phase_of

HLO = """HloModule jit_chunk, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias) }

FileNames
1 "loop.py"

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %exponential.2 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(chunk)/while/body/act/exp" source_file="loop.py" source_line=12}
}

%fused_computation.10 (param_0.1: f32[64], param_1: s32[]) -> f32[1,8] {
  %param_0.1 = f32[64]{0} parameter(0)
  %param_1 = s32[] parameter(1)
  %dynamic-slice.11 = f32[8]{0} dynamic-slice(%param_0.1, %param_1), dynamic_slice_sizes={8}
  ROOT %bitcast.12 = f32[1,8]{1,0} bitcast(%dynamic-slice.11)
}

%gather_body.13 (q: (s32[], f32[64])) -> (s32[], f32[64]) {
  %q = (s32[], f32[64]{0}) parameter(0)
  %get-tuple-element.14 = f32[64]{0} get-tuple-element(%q), index=1
  %get-tuple-element.15 = s32[] get-tuple-element(%q), index=0
  %dynamic-slice_bitcast_fusion.16 = f32[1,8]{1,0} fusion(%get-tuple-element.14, %get-tuple-element.15), kind=kLoop, calls=%fused_computation.10
  ROOT %tuple.17 = (s32[], f32[64]{0}) tuple(%get-tuple-element.15, %get-tuple-element.14)
}

%gather_cond.18 (r: (s32[], f32[64])) -> pred[] {
  %r = (s32[], f32[64]{0}) parameter(0)
  ROOT %constant.19 = pred[] constant(false)
}

%add.20 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.21 = f32[] add(%x, %y)
}

%branch.24 (b: f32[64]) -> f32[64] {
  %b = f32[64]{0} parameter(0)
  ROOT %copy.25 = f32[64]{0} copy(%b)
}

%body.3 (p: (f32[4], f32[64])) -> (f32[4], f32[64]) {
  %p = (f32[4]{0}, f32[64]{0}) parameter(0)
  %get-tuple-element.4 = f32[4]{0} get-tuple-element(%p), index=0
  %fusion.5 = f32[4]{0} fusion(%get-tuple-element.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(chunk)/while/body/act/exp"}
  %reduce-window.6 = f32[64]{0} reduce-window(%fusion.5), to_apply=%add.20, metadata={op_name="jit(chunk)/while/body/learn/cond/branch_1_fun/flush/reduce_window_sum" stack_frame_id=7}
  %sample_gather.7 = (s32[64]{0}, f32[64]{0}) custom-call(%reduce-window.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk)/while/body/learn/cond/branch_1_fun/sample/pallas_call"}
  %while.22 = (s32[], f32[64]{0}) while(%p), condition=%gather_cond.18, body=%gather_body.13, metadata={op_name="jit(chunk)/while/body/learn/cond/branch_1_fun/sample/vmap()/gather"}
  %reduce.23 = f32[] reduce(%reduce-window.6), to_apply=%add.20, metadata={op_name="jit(chunk)/while/body/insert_commit/reduce_sum"}
  %conditional.26 = f32[64]{0} conditional(%constant.19, %reduce-window.6, %reduce-window.6), branch_computations={%branch.24, %branch.24}, metadata={op_name="jit(chunk)/while/body/learn/cond"}
  %copy.8 = f32[4]{0} copy(%fusion.5)
  ROOT %tuple.9 = (f32[4]{0}, f32[64]{0}) tuple(%copy.8, %reduce-window.6), metadata={op_name="jit(chunk)/while"}
}
"""


def test_phase_of_takes_the_innermost_phase():
    assert phase_of("jit(chunk)/while/body/learn/cond/branch_1_fun/"
                    "flush/add") == "flush"
    assert phase_of("learn/cond/branch_1_fun/learner_update/jvp()/"
                    "reduce_sum") == "learner_update"
    assert phase_of("jit(chunk)/while/body/learn/cond") == "learn"
    assert phase_of("jit(chunk)/while") is None
    # a segment names a phase only when it is the phase's name
    assert phase_of("jit(chunk)/while/body/sampler/add") is None
    assert phase_of("jit(sample)/add") is None


def test_op_phases_of_handwritten_hlo():
    """Own ``op_name`` first; an instruction without one takes the phase
    of the fusion, ``while`` or reduce that calls its computation (the
    gather loop XLA made carries the gather's ``op_name`` on its
    ``while`` only), unless its callers disagree (the ``add`` reducer
    serves ``flush`` and ``insert_commit``); a conditional's branches
    inherit nothing (the copy XLA put in the learn branch)."""
    loop = {name: "sample" for name in (
        "dynamic-slice.11", "bitcast.12", "param_0.1", "param_1", "q",
        "get-tuple-element.14", "get-tuple-element.15",
        "dynamic-slice_bitcast_fusion.16", "tuple.17", "r", "constant.19",
        "while.22")}
    assert op_phases(HLO) == {
        "param_0": "act", "exponential.2": "act", "fusion.5": "act",
        "reduce-window.6": "flush", "sample_gather.7": "sample",
        "reduce.23": "insert_commit", "conditional.26": "learn", **loop}


def test_no_phase_is_named_like_a_primitive():
    import jax.extend.core.primitives as prims

    names = {p.name for p in vars(prims).values()
             if hasattr(p, "bind") and hasattr(p, "name")}
    assert "add" in names and not names & set(PHASES)
    assert len(set(PHASES)) == len(PHASES)


def strip_metadata(text: str) -> str:
    """A compiled module's text less its debug information: each
    instruction's ``metadata={...}`` and the file, function, location and
    stack-frame tables."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(
        line for line in text.splitlines()
        if not re.match(r"(\d+ |FileNames$|FunctionNames$|FileLocations$|"
                        r"StackFrames$)", line))


def instruction_names(text: str) -> set:
    return set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = ", text, re.M))


def _fused(**kw):
    from repro.agents.dqn import DQNConfig, make_dqn
    from repro.core.replay import PrioritizedReplay, ReplayConfig
    from repro.envs.classic import make_vec
    from repro.runtime.executors import AsyncExecutor, FusedExecutor
    from repro.runtime.loop import LoopConfig

    env_fn = functools.partial(make_vec, "cartpole")
    spec, _, _ = env_fn(1)
    agent = make_dqn(spec, DQNConfig(hidden=(32, 32)))
    example = {"obs": jnp.zeros((4,)), "action": jnp.zeros((), jnp.int32),
               "reward": jnp.zeros(()), "next_obs": jnp.zeros((4,)),
               "done": jnp.zeros(())}
    replay = PrioritizedReplay(ReplayConfig(capacity=2 ** 14, fanout=128),
                               example)
    cfg = LoopConfig(batch_size=64, update_interval=2, warmup=0)
    if kw:
        return AsyncExecutor(agent, replay, env_fn, cfg, 16, scan_chunk=2,
                             **kw)
    return FusedExecutor(agent, replay, env_fn, cfg, 16, scan_chunk=2)


@pytest.fixture(scope="module")
def fused():
    """A tiny fused chunk (16 actors, 8 learns of 64 rows an iteration,
    2^14 rows), its state and compiled text."""
    ex = _fused()
    state = jax.jit(ex.init)(jax.random.PRNGKey(0))
    return ex, state, ex.lower_chunk(state).compile().as_text()


def test_fused_chunk_maps_every_phase_but_reduce_and_publish(fused):
    ex, state, text = fused
    mapped = ex.op_phases(state)
    assert mapped == op_phases(text)
    assert set(mapped.values()) == set(PHASES) - {"grad_reduce", "publish"}
    assert set(mapped) <= instruction_names(text)


def test_async_chunk_maps_publish():
    ex = _fused(publish_interval=2, max_staleness=0)
    state = jax.eval_shape(ex.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert "publish" in set(ex.op_phases(state).values())


def test_scopes_change_no_op(fused, monkeypatch):
    """The chunk built with every named scope a no-op compiles to the
    same module, debug information aside."""
    _, state, text = fused
    scoped = []
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: scoped.append(name)
                        or contextlib.nullcontext())
    bare = _fused().lower_chunk(state).compile().as_text()
    assert set(scoped) == set(PHASES) - {"grad_reduce", "publish"}
    assert strip_metadata(bare) == strip_metadata(text)


def _host_events(tdir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)
    assert len(path) == 1
    return [(line.name, e.name, dict(e.stats))
            for plane in ProfileData.from_file(path[0]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.fixture(scope="module")
def traced(fused, tmp_path_factory):
    """Three chunks of the tiny fused executor under the profiler."""
    ex, state, _ = fused
    state = jax.tree.map(jnp.copy, state)     # run_chunk donates the replay
    state, m = ex.run_chunk(state)            # compiled outside the trace
    jax.block_until_ready(m)
    first = ex.chunks_dispatched
    tdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tdir)
    try:
        for _ in range(3):
            state, m = ex.run_chunk(state)
        jax.block_until_ready(m)
    finally:
        jax.profiler.stop_trace()
    return first, _host_events(tdir)


def test_run_chunk_emits_one_span_per_call(fused, traced):
    ex = fused[0]
    first, events = traced
    spans = [st for _, name, st in events if name == "run_chunk"]
    assert [s["step_num"] for s in spans] == [first, first + 1, first + 2]
    assert {s["length"] for s in spans} == {ex.scan_chunk}


def test_cpu_profile_ops_join_the_map(fused, traced):
    """A CPU profile names each thunk by ``hlo_op``: every op of the
    chunk is an instruction of the compiled module, with a phase or
    none (unattributed), and the phases' ops ran."""
    ex, state, text = fused
    mapped = ex.op_phases(state)
    ops = {st["hlo_op"] for _, _, st in traced[1]
           if st.get("hlo_module") == "jit_chunk" and "hlo_op" in st}
    assert ops and ops <= instruction_names(text)
    assert {"act", "sample", "learner_update", "write_back"} <= {
        mapped[op] for op in ops if op in mapped}


SHARDED = r"""
import contextlib, functools, json, os, re, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.agents.dqn import DQNConfig, make_dqn
from repro.core.distributed import ShardedPrioritizedReplay, ShardedReplayConfig
from repro.envs.classic import make_vec
from repro.launch.mesh import data_mesh
from repro.runtime.executors import ShardedExecutor
from repro.runtime.loop import LoopConfig
from repro.runtime.phases import op_phases
assert jax.device_count() == 4

def build():
    env_fn = functools.partial(make_vec, "cartpole")
    spec, _, _ = env_fn(1)
    example = {"obs": jnp.zeros((4,)), "action": jnp.zeros((), jnp.int32),
               "reward": jnp.zeros(()), "next_obs": jnp.zeros((4,)),
               "done": jnp.zeros(())}
    replay = ShardedPrioritizedReplay(
        ShardedReplayConfig(capacity_per_shard=2 ** 12, fanout=128), example)
    return ShardedExecutor(make_dqn(spec, DQNConfig(hidden=(32, 32))), replay,
                           env_fn, LoopConfig(batch_size=64, update_interval=8,
                                              warmup=0),
                           16, data_mesh(4), scan_chunk=2)

ex = build()
state = ex.init(jax.random.PRNGKey(0))
text = ex.lower_chunk(state).compile().as_text()
scoped = []
jax.named_scope = lambda name: scoped.append(name) or contextlib.nullcontext()
bare = build().lower_chunk(state).compile().as_text()
reduces = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = [^\n]*? all-reduce(?:-start)?\(",
                     text, re.M)
loop = text[text.index("\nENTRY"):]
print(json.dumps({"text": text, "bare": bare, "phases": op_phases(text),
                  "scoped": sorted(set(scoped)),
                  "reduces": reduces,
                  "entry_reduces": [r for r in reduces if "%" + r + " = " in loop
                                    or "\n  " + r + " = " in loop]}))
"""


@pytest.fixture(scope="module")
def sharded():
    """A tiny ShardedExecutor chunk on 4 virtual CPU devices, compiled as
    shipped and with every named scope a no-op (subprocess: the device
    count is set before jax starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), "src"),
                    os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", SHARDED], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_all_reduces_are_sample_and_grad_reduce(sharded):
    """The PER stats' psum/pmax are ``sample``, the gradient pmean is
    ``grad_reduce``; only the per-chunk metric reduce, outside the step,
    has no phase."""
    phases = sharded["phases"]
    in_step = {phases[r] for r in sharded["reduces"] if r in phases}
    assert in_step == {"sample", "grad_reduce"}
    loose = [r for r in sharded["reduces"] if r not in phases]
    assert len(loose) == 1 and loose == sharded["entry_reduces"]
    assert set(phases.values()) == set(PHASES) - {"publish"}


def test_sharded_scopes_change_no_op(sharded):
    assert sharded["scoped"] == sorted(set(PHASES) - {"publish"})
    assert strip_metadata(sharded["bare"]) == strip_metadata(sharded["text"])
