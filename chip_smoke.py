"""Bring-up smoke test: the system's main paths on a TPU, through the
entry points a user calls, at real sizes, each checked against a plain
reference.

    PYTHONPATH=src python chip_smoke.py               # one chip
    PYTHONPATH=src python chip_smoke.py --four-chips  # one 4-chip host

One chip runs two phases:

* ``train`` — the paper's pipeline through ``FusedExecutor``: double-Q
  DQN on 64 vectorized CartPole actors, lazy replay transactions, and a
  2^20-leaf fanout-128 prioritized replay on the Pallas backend (the
  compiled fused sample+gather kernel), learner batch 256.  Checked
  against the same run on the XLA backend from the same seed, and
  kernel by kernel against XLA on a full tree of random priorities.
* ``serve`` — the continuous-batching ``ActorServer`` at granite-8b's
  published widths cut to 4 of 36 layers: 16 requests on 8 slots.
  Prefill and decode logits are checked against a float32 forward.

``--four-chips`` runs only the sharded executor (the paper's parallel
learners) over ``data_mesh(4)`` and ``pod_data_mesh(2, 2)``.

Nothing is caught: a failed phase raises and the exit code is not 0.
Without a TPU the script exits before any phase.  The last line of
stdout is one JSON object naming the device; a copy of the phase
results is written under ``--out``.
"""

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

FANOUT = 128
# f32 has a 24-bit significand: one unit in the last place of the total
# priority mass is total * 2^-24
F32_EPS = 2.0 ** -24


def log(*parts):
    print(*parts, flush=True)


class CompileClock:
    """Seconds JAX spends in backend compilation (a persistent-cache hit
    counts only its retrieval), summed since the last ``take``."""

    def __init__(self):
        import jax

        self._secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._secs += secs

    def take(self) -> float:
        secs, self._secs = self._secs, 0.0
        return secs


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform "
            f"{devices[0].platform!r}); this smoke test runs only on a TPU")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, found "
                         f"{len(devices)}")
    return devices


def cartpole_dqn():
    import jax.numpy as jnp

    from repro.agents.dqn import DQNConfig, make_dqn
    from repro.envs.classic import make_vec

    env_fn = functools.partial(make_vec, "cartpole")
    spec, _, _ = env_fn(1)
    example = {
        "obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "action": jnp.zeros((), jnp.int32),
        "reward": jnp.zeros(()),
        "next_obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "done": jnp.zeros(()),
    }
    return env_fn, make_dqn(spec, DQNConfig(double_q=True)), example


# -- train ------------------------------------------------------------------


class OpsSpy:
    """TreeOps wrapper that records which ops the loop traced."""

    def __init__(self, ops):
        self._ops = ops
        self.name = ops.name
        self.calls = set()

    def __getattr__(self, op):
        fn = getattr(self._ops, op)

        def traced(*args, **kwargs):
            self.calls.add(op)
            return fn(*args, **kwargs)
        return traced


def train_run(backend, *, capacity, n_envs, batch, update_interval, warmup,
              scan_chunk, chunks, seed, clock):
    """One FusedExecutor run; returns (per-iteration metrics, facts)."""
    import jax
    import numpy as np

    from repro.core.replay import PrioritizedReplay, ReplayConfig
    from repro.kernels import ops as kernel_ops
    from repro.runtime.executors import FusedExecutor
    from repro.runtime.loop import LoopConfig

    env_fn, agent, example = cartpole_dqn()
    replay = PrioritizedReplay(
        ReplayConfig(capacity=capacity, fanout=FANOUT, backend=backend),
        example)
    spy = replay.ops = OpsSpy(replay.ops)
    cfg = LoopConfig(batch_size=batch, warmup=warmup, epsilon=0.2,
                     update_interval=update_interval)
    ex = FusedExecutor(agent, replay, env_fn, cfg, n_envs,
                       scan_chunk=scan_chunk)
    state = ex.init(jax.random.PRNGKey(seed))
    clock.take()
    t0 = time.perf_counter()
    hlo = ex.lower_chunk(state).compile().as_text()
    compile_s = clock.take()
    lower_s = time.perf_counter() - t0

    facts = {"backend": backend, "compile_s": compile_s,
             "lower_and_compile_wall_s": lower_s,
             "fused": replay.config.fused_sample_gather_resolved,
             "ops_traced": sorted(spy.calls)}
    if backend == "pallas":
        # the kernel path, not the size-based XLA fallback of
        # kernels/ops.py, and the fused kernel inside the chunk program
        facts["kernel_path_ok"] = kernel_ops.kernel_path_ok(replay.spec)
        facts["sample_gather_kernel_in_program"] = (
            "custom-call" in hlo and "sample_gather" in hlo)
        assert facts["kernel_path_ok"], (
            f"tree of {replay.spec.total_size * 4} B exceeds the kernel "
            "budget: kernels/ops.py would take the XLA path")
        if jax.default_backend() == "tpu":
            # fused_sample_gather=None resolves by platform: on by TPU
            assert facts["fused"], "fused sample+gather did not resolve on"
            assert facts["sample_gather_kernel_in_program"], (
                "no sample_gather custom call in the compiled chunk")
    sampling = {"sample_gather"} if facts["fused"] else {"sample", "gather"}
    assert spy.calls & {"sample", "gather", "sample_gather"} == sampling, (
        spy.calls, sampling)

    history = []
    state, metrics = ex.run_chunk(state)
    history.append(metrics)
    jax.block_until_ready(metrics)
    t1 = time.perf_counter()
    for _ in range(chunks - 1):
        state, metrics = ex.run_chunk(state)
        history.append(metrics)
    jax.block_until_ready(metrics)
    steady_s = time.perf_counter() - t1
    facts["steady_iters_per_s"] = ((chunks - 1) * scan_chunk / steady_s
                                   if chunks > 1 else float("nan"))
    facts["compile_s_after_first_chunk"] = clock.take()
    hist = jax.tree.map(lambda *xs: np.concatenate(
        [np.asarray(x) for x in xs]), *history)
    facts["env_steps"] = int(hist["env_steps"][-1])
    facts["learn_steps"] = int(hist["learn_steps"][-1])
    facts["buffer_size"] = int(hist["buffer_size"][-1])
    return hist, facts


def kernel_check(*, capacity, batch, seed):
    """TreeOps.sample_gather on both backends, same tree, same draws.

    Every fetched row must equal ``storage[idx]`` exactly and every
    returned priority must equal the leaf it names.  The two backends'
    indices may differ only where a draw falls within f32 rounding of a
    prefix-sum boundary between two neighbouring leaves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sumtree
    from repro.core.tree_ops import get_tree_ops

    spec = sumtree.make_spec(capacity, FANOUT)
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    pri = jax.random.uniform(ks[0], (capacity,), minval=0.01, maxval=1.0)
    tree = sumtree.rebuild(spec, sumtree.write_leaves(
        spec, sumtree.init(spec), jnp.arange(capacity), pri, unique=True))
    storage = {
        "obs": jax.random.normal(ks[1], (capacity, 4)),
        "action": jax.random.randint(ks[2], (capacity,), 0, 2),
        "reward": jax.random.normal(ks[3], (capacity,)),
        "next_obs": jax.random.normal(ks[4], (capacity, 4)),
        "done": jax.random.bernoulli(ks[5], 0.05, (capacity,)).astype(
            jnp.float32),
    }
    u = jax.random.uniform(ks[6], (batch,))
    host = {k: np.asarray(v) for k, v in storage.items()}
    pri_h = np.asarray(pri)

    idx = {}
    for backend in ("pallas", "xla"):
        ops = get_tree_ops(backend)
        fn = jax.jit(ops.sample_gather, static_argnums=0)
        i, p, items = jax.device_get(fn(spec, tree, u, storage))
        assert i.min() >= 0 and i.max() < capacity, (backend, i.min(),
                                                     i.max())
        for name, rows in items.items():
            np.testing.assert_array_equal(
                rows, host[name][i], err_msg=f"{backend} rows of {name}")
        np.testing.assert_array_equal(p, pri_h[i],
                                      err_msg=f"{backend} priorities")
        idx[backend] = i

    # where do the disagreements sit?  Exact prefix sums in float64 of
    # the same f32 leaves, and the exact target of each draw
    cum = np.cumsum(pri_h.astype(np.float64))
    total = cum[-1]
    target = np.clip(np.asarray(u, np.float64), 1e-12, 1.0 - 1e-7) * total
    differ = np.nonzero(idx["pallas"] != idx["xla"])[0]
    ulps = []
    for d in differ:
        lo, hi = sorted((int(idx["pallas"][d]), int(idx["xla"][d])))
        assert hi == lo + 1, (d, lo, hi)   # neighbours only
        ulps.append(abs(target[d] - cum[lo]) / (total * F32_EPS))
    # Both descents sum K=128 f32 children per level over 3 levels; the
    # partial sums each round by at most half an ulp of their own size,
    # so the two backends can disagree only on draws that sit within a
    # few ulps of the total from a boundary.  Interpret mode on a CPU
    # shows at most 0.85 ulp here and a TPU v5e 4.1 (its multi-pass f32
    # matmul rounds the prefix sums more); 8 ulps leaves room for that.
    ulp_bound = 8.0
    # At 2^20 leaves a leaf holds ~16 ulps of the total on average, so
    # a draw falls within ~1 ulp of a boundary (the rounding actually
    # seen) about 1 time in 8; a quarter of the batch doubles that and
    # still fails a descent that is off for a whole class of draws.
    count_bound = batch // 4
    max_ulps = max(ulps, default=0.0)
    assert max_ulps <= ulp_bound, (max_ulps, ulp_bound)
    assert len(differ) <= count_bound, (len(differ), count_bound)
    return {"capacity": capacity, "batch": batch,
            "rows_exact": True, "index_disagreements": int(len(differ)),
            "disagreement_bound": count_bound,
            "max_boundary_distance_ulps": float(max_ulps),
            "ulp_bound": ulp_bound}


def train_phase(*, capacity=2 ** 20, n_envs=64, batch=256,
                update_interval=16, warmup=1024, scan_chunk=8, chunks=6,
                min_learns=32, seed=0, clock):
    """Pallas run, XLA reference run, kernel-level row check."""
    import numpy as np

    kw = dict(capacity=capacity, n_envs=n_envs, batch=batch,
              update_interval=update_interval, warmup=warmup,
              scan_chunk=scan_chunk, chunks=chunks, seed=seed, clock=clock)
    h_pal, f_pal = train_run("pallas", **kw)
    path = ("fused sample+gather kernel" if f_pal["fused"]
            else "split sample and gather kernels")
    log(f"train[pallas]: capacity {capacity}, fanout {FANOUT}, {n_envs} "
        f"actors, batch {batch}; sample path: {path} "
        f"(kernel_path_ok={f_pal['kernel_path_ok']}, in compiled program="
        f"{f_pal['sample_gather_kernel_in_program']}, traced ops "
        f"{f_pal['ops_traced']})")
    log(f"train[pallas]: compile {f_pal['compile_s']:.2f} s; "
        f"env_steps {f_pal['env_steps']}, learn_steps "
        f"{f_pal['learn_steps']}, buffer {f_pal['buffer_size']}; steady "
        f"{f_pal['steady_iters_per_s']:.2f} it/s (smoke reading)")
    h_xla, f_xla = train_run("xla", **kw)
    log(f"train[xla]:    compile {f_xla['compile_s']:.2f} s; env_steps "
        f"{f_xla['env_steps']}, learn_steps {f_xla['learn_steps']}, buffer "
        f"{f_xla['buffer_size']}; steady {f_xla['steady_iters_per_s']:.2f} "
        "it/s (smoke reading)")

    for k in ("env_steps", "learn_steps", "buffer_size"):
        np.testing.assert_array_equal(h_pal[k], h_xla[k], err_msg=k)
    learns = f_pal["learn_steps"]
    assert learns >= min_learns, f"only {learns} learns: raise chunks"
    for name, h in (("pallas", h_pal), ("xla", h_xla)):
        assert np.isfinite(h["loss"]).all(), f"{name} loss not finite"
    log("train: counters identical to the XLA reference, losses finite")

    kc = kernel_check(capacity=capacity, batch=batch, seed=seed + 1)
    log(f"train[kernel]: rows exact; {kc['index_disagreements']} of "
        f"{batch} indices differ from XLA (bound "
        f"{kc['disagreement_bound']}), each within "
        f"{kc['max_boundary_distance_ulps']:.3f} ulps of a boundary "
        f"(bound {kc['ulp_bound']})")
    return {"pallas": f_pal, "xla": f_xla, "kernel_check": kc}


# -- serve ------------------------------------------------------------------


def granite_cut(layers=4):
    """granite-8b at its published widths, cut in depth (the stack is
    uniform, so any depth keeps whole periods)."""
    from repro.configs import granite_8b

    return dataclasses.replace(granite_8b.CONFIG, num_layers=layers)


def serve_phase(cfg, *, slots=8, max_len=2048, buckets=(256, 512, 1024),
                requests=16, gen=64, prompt_lens=(64, 1024), ref_steps=4,
                rel_tol=5e-2, seed=0, clock):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import backbone
    from repro.models.config import NO_SHARDING
    from repro.serve import ActorServeConfig, ActorServer

    params = jax.jit(functools.partial(backbone.init_params, cfg))(
        jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    rng = np.random.RandomState(seed)
    lens = rng.randint(prompt_lens[0], prompt_lens[1] + 1, size=requests)
    prompts = [rng.randint(0, cfg.vocab_size, size=int(n)) for n in lens]

    clock.take()
    server = ActorServer(cfg, params, ActorServeConfig(
        slots=slots, max_len=max_len, buckets=tuple(buckets),
        max_new_tokens=gen))
    t0 = time.perf_counter()
    handles = [server.submit(p) for p in prompts]
    server.drain(timeout=900)
    wall = time.perf_counter() - t0
    completions = [h.result(0) for h in handles]
    s = server.stats()
    compile_s = clock.take()

    generated = sum(len(c.tokens) for c in completions)
    assert generated == requests * gen, (generated, requests, gen)
    assert s["generated_tokens"] == generated
    assert s["admissions"] + s["decoded_tokens"] == requests * gen
    touched = {min(b for b in buckets if b >= n) for n in lens}
    assert s["prime_compiles"] == len(touched), (s["prime_compiles"],
                                                 touched)
    assert s["decode_compiles"] == 1, s["decode_compiles"]
    log(f"serve: {cfg.name} x{cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}: "
        f"{n_params / 1e9:.3f} B params")
    log(f"serve: {requests} requests x {gen} tokens on {slots} slots, "
        f"prompts {int(lens.min())}-{int(lens.max())} tokens, buckets "
        f"{tuple(buckets)}: {s['prime_compiles']} prefill compiles, "
        f"{s['decode_compiles']} decode compile, compile {compile_s:.2f} s")
    log(f"serve: prefill {s['admissions']} prompts ({int(lens.sum())} "
        f"prompt tokens) in {s['prefill_s']:.3f} s; decode {s['steps']} "
        f"steps, {s['decoded_tokens']} tokens in {s['decode_s']:.3f} s; "
        f"admissions + decoded == requests x gen ({requests * gen}); "
        f"wall {wall:.2f} s (smoke reading)")
    del server

    # reference: the first prompt through backbone.prefill/decode_step,
    # the functions token_dqn.serve_step wraps, solo
    prompt = jnp.asarray(prompts[0], jnp.int32)[None]
    n = prompt.shape[1]
    prefill = jax.jit(lambda p, t: backbone.prefill(
        cfg, NO_SHARDING, p, t, max_len=max_len))
    decode = jax.jit(lambda p, c, t: backbone.decode_step(
        cfg, NO_SHARDING, p, c, t))
    logits, cache = prefill(params, prompt)
    rows = [np.asarray(logits[0], np.float32)]
    tok = jnp.argmax(logits[0, -1]).astype(jnp.int32)
    solo = [int(tok)]
    for step in range(gen - 1):
        logits, cache = decode(params, cache, tok.reshape(1, 1))
        if step < ref_steps:
            rows.append(np.asarray(logits[0], np.float32))
        tok = jnp.argmax(logits[0, -1]).astype(jnp.int32)
        solo.append(int(tok))
    got = np.concatenate(rows)                       # (n + ref_steps, V)
    del cache

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    del params
    full = jnp.concatenate(
        [prompt, jnp.asarray([solo[:ref_steps]], jnp.int32)], axis=1)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: backbone.forward(
            cfg32, NO_SHARDING, p, t))(params32, full)
    ref = np.asarray(ref[0], np.float32)
    del params32
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all()
    rel = (np.linalg.norm(got - ref, axis=-1)
           / np.maximum(np.linalg.norm(ref, axis=-1), 1e-30))
    # bf16 keeps 8 significant bits: every stored activation, weight and
    # attention probability of the served model rounds by up to 2^-9,
    # and four layers of residual adds compound that to ~1-2% of a
    # logit row (1.5% in the reduced-width CPU rehearsal).  A wrong
    # position, a stale cache slot or a RoPE phase error moves a row by
    # O(1), so 5% separates the two.
    assert rel.max() <= rel_tol, (float(rel.max()), rel_tol)
    batched = completions[0].tokens
    agree = sum(a == b for a, b in zip(batched, solo))
    first_diff = next((i for i, (a, b) in enumerate(zip(batched, solo))
                       if a != b), gen)
    log(f"serve[ref]: prompt {n} tokens + {ref_steps} decode steps vs f32 "
        f"forward at highest precision: max row rel-L2 {rel.max():.5f} "
        f"(prefill {rel[:n].max():.5f}, decode {rel[n:].max():.5f}; "
        f"bound {rel_tol})")
    log(f"serve[ref]: continuous-batched vs solo greedy decode: {agree} of "
        f"{gen} tokens agree, first difference at token {first_diff} "
        "(reported, not asserted)")
    return {"model": cfg.name, "layers": cfg.num_layers,
            "params": int(n_params), "requests": requests, "gen": gen,
            "slots": slots, "prime_compiles": int(s["prime_compiles"]),
            "decode_compiles": int(s["decode_compiles"]),
            "compile_s": compile_s, "prefill_s": s["prefill_s"],
            "decode_s": s["decode_s"], "wall_s": wall,
            "ref_prompt_len": int(n),
            "max_rel_l2": float(rel.max()), "rel_tol": rel_tol,
            "solo_tokens_agree": int(agree),
            "solo_first_difference": int(first_diff)}


# -- four chips -------------------------------------------------------------


def sharded_phase(*, capacity_per_shard=2 ** 18, backend="pallas",
                  strict_iters=8, iterations=12, seed=7, clock):
    """data_mesh(4) ≡ pod_data_mesh(2, 2) from one seed; compressed vs
    uncompressed 2×2; placement of the shards (the checks and
    tolerances of tests/test_pod_executor.py).

    The 1-D mesh means the gradients over four shards in one step, the
    2×2 mesh over ``data`` and then ``pod``: the same value, rounded in
    a different order.  Once a rounding difference flips one PER draw
    the two runs sample different batches, so the ulp-level checks
    hold over ``strict_iters`` (two chunks), before any draw can flip."""
    import jax
    import numpy as np

    from repro.core.distributed import (ShardedPrioritizedReplay,
                                        ShardedReplayConfig)
    from repro.launch.mesh import data_mesh, pod_data_mesh
    from repro.runtime.executors import ShardedExecutor
    from repro.runtime.loop import LoopConfig

    env_fn, agent, example = cartpole_dqn()
    cfg = LoopConfig(batch_size=32, warmup=8, epsilon=0.2)
    key = jax.random.PRNGKey(seed)

    def run(mesh, axes, compress=False, iters=iterations):
        replay = ShardedPrioritizedReplay(
            ShardedReplayConfig(capacity_per_shard=capacity_per_shard,
                                fanout=FANOUT, backend=backend,
                                axis_names=axes), example)
        ex = ShardedExecutor(agent, replay, env_fn, cfg, n_envs=8, mesh=mesh,
                             scan_chunk=4, compress_pod_reduce=compress)
        state, hist = ex.train(iters, key)
        return state, {k: np.asarray(v) for k, v in hist.items()}

    clock.take()
    s1, h1 = run(data_mesh(4), ("data",), iters=strict_iters)
    s2, h2 = run(pod_data_mesh(2, 2), ("pod", "data"), iters=strict_iters)
    for k in ("env_steps", "learn_steps", "buffer_size"):
        np.testing.assert_array_equal(h1[k], h2[k], err_msg=k)
    np.testing.assert_allclose(h1["mean_episode_return"],
                               h2["mean_episode_return"], rtol=1e-6)
    np.testing.assert_allclose(h1["loss"], h2["loss"], rtol=1e-3, atol=1e-6)
    p_diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree.leaves(s1.agent.params),
                                 jax.tree.leaves(s2.agent.params)))
    assert p_diff <= 1e-5, p_diff
    log(f"four-chips: data_mesh(4) vs pod_data_mesh(2,2) [{backend}, "
        f"{capacity_per_shard} leaves/shard, {strict_iters} iterations]: "
        "counters identical, "
        f"env_steps {int(h1['env_steps'][-1])}, learn_steps "
        f"{int(h1['learn_steps'][-1])}, max |loss diff| "
        f"{float(np.max(np.abs(h1['loss'] - h2['loss']))):.3g}, max "
        f"|param diff| {p_diff:.3g} (bound 1e-5)")

    s2, h2 = run(pod_data_mesh(2, 2), ("pod", "data"))
    sc, hc = run(pod_data_mesh(2, 2), ("pod", "data"), compress=True)
    for k in ("env_steps", "learn_steps", "buffer_size"):
        np.testing.assert_array_equal(h2[k], hc[k], err_msg=k)
    assert np.isfinite(hc["loss"]).all()
    c_diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree.leaves(s2.agent.params),
                                 jax.tree.leaves(sc.agent.params)))
    assert c_diff <= 0.1, c_diff
    ef = jax.tree.leaves(sc.ef_error)
    assert ef[0].shape[0] == 4, ef[0].shape
    log(f"four-chips: compressed vs uncompressed 2x2 [{iterations} "
        "iterations]: counters identical, "
        f"losses finite, max |param diff| {c_diff:.3g} (bound 0.1), EF "
        f"buffer leading axis {ef[0].shape[0]}")

    def devices_of(leaf):
        shards = leaf.addressable_shards
        assert all(sh.data.shape[0] == 1 for sh in shards), leaf.shape
        return sorted(sh.device.id for sh in shards)

    for name, tree in (("replay", sc.replay), ("ef_error", sc.ef_error)):
        for leaf in jax.tree.leaves(tree):
            ids = devices_of(leaf)
            assert len(set(ids)) == 4, (name, ids)
    for leaf in jax.tree.leaves(sc.agent.params):
        copies = [np.asarray(sh.data) for sh in leaf.addressable_shards]
        assert len(copies) == 4
        assert all(np.array_equal(copies[0], c) for c in copies[1:])
    log("four-chips: every replay shard and EF buffer on its own device; "
        "params identical on all 4 devices after the reduce")
    return {"compile_s": clock.take(), "param_diff_1d_vs_2x2": p_diff,
            "param_diff_compressed": c_diff,
            "learn_steps": int(h1["learn_steps"][-1])}


# -- entry ------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded executor on 4 chips")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for the JSON report and TPU logs")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(args.out, "tpu_logs"))
    devices = require_tpu(4 if args.four_chips else 1)
    os.makedirs(args.out, exist_ok=True)

    import jax

    from repro import compile_cache

    cache_dir = compile_cache.enable()
    clock = CompileClock()
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    t0 = time.perf_counter()
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)}}
    if args.four_chips:
        report["four_chips"] = sharded_phase(clock=clock)
    else:
        report["train"] = train_phase(clock=clock)
        report["serve"] = serve_phase(granite_cut(), clock=clock)
    report["wall_s"] = time.perf_counter() - t0
    with open(os.path.join(args.out, "four_chips.json" if args.four_chips
                           else "one_chip.json"), "w") as f:
        json.dump(report, f, indent=1, default=float)
    log(f"all phases passed in {report['wall_s']:.1f} s")
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)


if __name__ == "__main__":
    main()
