"""Replay-transaction microbenchmark: the lazy-writing payoff, isolated.

Replay is the throughput ceiling of every executor backend (the paper's
§IV bottleneck analysis; Reverb and Spreeze reach the same conclusion),
so this benchmark times the *loop-shaped replay transaction* alone — one
iteration's worth of buffer work with the learner compute stripped out:

    insert_begin → [flush] → sample(+gather) → update_priorities
                 → insert_commit

swept over the axes the tentpole optimization changed:

  * ``mode``  — ``eager`` (each op propagates up the tree: three full
    passes per transaction, the pre-optimization baseline) vs ``lazy``
    (leaf-only writes + ONE merged propagation pass at the sample
    boundary, DESIGN.md §9);
  * ``fused`` — split sample + per-leaf gather kernels vs the fused
    sample+gather kernel (pallas backend only; the xla backend has no
    separate kernel launches to fuse);
  * ``backend`` — xla | pallas (interpret mode on CPU).

The metric is **replay ops/s**: transaction throughput × ops per
transaction (``insert_batch`` inserts + ``sample_batch`` samples +
``sample_batch`` priority updates), median-of-N with recorded dispersion
(benchmarks/timing.py).  ``--emit-json DIR`` writes ``BENCH_replay.json``
(schema: benchmarks/schema.py, figure "replay"); the committed repo-root
baseline is diffed by benchmarks/compare.py and must show the lazy mode
beating the eager mode per backend (asserted in
tests/test_replay_transactions.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.timing import REPEATS

REPLAY_JSON = "BENCH_replay.json"

# Per-backend sizing, chosen so the tree-propagation work (what lazy
# writing removes) is a visible fraction of the transaction on *that*
# backend: the XLA arms use a 64Ki-leaf tree (big enough that the three
# per-pass copies/scatters dominate fixed per-op costs — at a few Ki
# leaves the common-mode sample cost drowns the delta in runner noise);
# the pallas arms, which run in *interpret* mode on CPU, use an 8Ki
# tree (at 64Ki the interpreted descent matmuls dominate everything and
# no update-path difference is measurable).  Both fit the kernels' VMEM
# budget.  insert batch = capacity/512, sample batch = 2× that.
SIZES = {
    "xla": (65536, 128, 256),      # (capacity, insert_batch, sample_batch)
    "pallas": (8192, 64, 128),
}
OBS_DIM = 4           # cartpole-shaped transition payload


def _make_buffer(backend: str, fused: bool, fanout: int, capacity: int):
    from repro.core.replay import PrioritizedReplay, ReplayConfig

    example = {
        "obs": jnp.zeros((OBS_DIM,), jnp.float32),
        "action": jnp.zeros((), jnp.int32),
        "reward": jnp.zeros(()),
        "next_obs": jnp.zeros((OBS_DIM,), jnp.float32),
        "done": jnp.zeros(()),
    }
    rb = PrioritizedReplay(
        ReplayConfig(capacity=capacity, fanout=fanout, backend=backend,
                     fused_sample_gather=fused), example)
    return rb, example


def _transaction_scan(rb, example, lazy: bool, iters: int,
                      insert_batch: int, sample_batch: int):
    """``iters`` loop-shaped transactions inside one jitted ``lax.scan``
    (replay state donated) — the same execution shape as the executors'
    chunk programs, so per-call Python dispatch stays out of the
    measurement."""

    def txn(state, key):
        k_items, k_sample, k_td = jax.random.split(key, 3)
        state, slots = rb.insert_begin(state, insert_batch, lazy=lazy)
        if lazy:
            state = rb.flush(state)
        idx, items, w = rb.sample(state, k_sample, sample_batch)
        # thread a live (but negligible) dependency on the gathered items
        # and weights into the write-back so XLA cannot dead-code the
        # gather/weight computation out of the measured loop
        touch = 1e-12 * (jnp.mean(items["obs"]) + jnp.mean(w))
        td = jax.random.uniform(k_td, (sample_batch,), minval=0.01,
                                maxval=2.0) + touch
        state = rb.update_priorities(state, idx, td, lazy=lazy)
        fresh = jax.tree.map(
            lambda x: jax.random.normal(
                k_items, (insert_batch,) + tuple(x.shape)).astype(x.dtype),
            example)
        return rb.insert_commit(state, slots, fresh, lazy=lazy)

    def chunk(state, key):
        def body(s, i):
            return txn(s, jax.random.fold_in(key, i)), ()
        return jax.lax.scan(body, state, jnp.arange(iters))[0]

    return jax.jit(chunk, donate_argnums=(0,))


def _make_probe(backend: str, mode: str, fused: bool, iters: int,
                fanout: int):
    """Compile one arm's scanned transaction chunk and return a warmed
    ``probe() → replay ops/s`` closure."""
    capacity, insert_batch, sample_batch = SIZES[backend]
    rb, example = _make_buffer(backend, fused, fanout, capacity)
    chunk = _transaction_scan(rb, example, mode == "lazy", iters,
                              insert_batch, sample_batch)
    key = jax.random.PRNGKey(0)

    def fill(state):  # warm buffer: every slot valid, non-trivial tree
        return rb.insert(state, jax.tree.map(
            lambda x: jax.random.normal(
                key, (capacity,) + tuple(x.shape)).astype(x.dtype), example))

    state = fill(rb.init())
    state = chunk(state, key)                     # compile + cold pass
    jax.block_until_ready(state.tree)
    holder = [state, 0]

    def probe():
        holder[1] += 1
        t0 = time.perf_counter()
        holder[0] = chunk(holder[0], jax.random.fold_in(key, holder[1]))
        jax.block_until_ready(holder[0].tree)
        dt = time.perf_counter() - t0
        ops = insert_batch + 2 * sample_batch     # insert + sample + update
        return ops * iters / dt

    return probe


def replay_points(smoke: bool = False):
    """The committed sweep.

    Two comparisons ride in one payload:

      * **eager vs lazy** — like-for-like arms at ``fused=False`` per
        backend and fanout, where the propagation-pass difference is
        the dominant term.  The acceptance test
        (tests/test_replay_transactions.py) asserts lazy > eager on
        every such pair of the committed file;
      * **fused vs split** — the pallas sample+gather arms at fixed
        ``mode="lazy"``.  On CPU these run in Pallas *interpret* mode,
        where per-grid-step Python interpretation dominates — the
        fused-vs-split delta recorded here is qualitative (the HBM
        round trip it removes only matters compiled on TPU), so it is
        reported, not gated.
    """
    arms = [
        # (backend, mode, fused, fanout)
        ("xla", "eager", False, 64),
        ("xla", "lazy", False, 64),
        ("xla", "eager", False, 128),
        ("xla", "lazy", False, 128),
        ("pallas", "eager", False, 128),
        ("pallas", "lazy", False, 128),
        ("pallas", "lazy", True, 128),
    ]
    import statistics

    # compile + warm every arm first, then probe the arms round-robin:
    # background load on a shared runner drifts over minutes, so probing
    # arm-by-arm would hand different arms different machines — the
    # interleaving gives every arm the same load profile per round and
    # the per-arm median rejects the bursts
    probes = []
    for backend, mode, fused, fanout in arms:
        # sized so one scanned probe runs ≥ ~100ms (timer noise floor);
        # interpret-mode pallas is orders slower — keep its loop short
        iters = ((6 if backend == "pallas" else 500) if smoke
                 else (12 if backend == "pallas" else 2000))
        probe = _make_probe(backend, mode, fused, iters, fanout)
        probe()                                   # discard the warm-up pass
        probes.append(((backend, mode, fused, fanout), probe))
    samples = {key: [] for key, _ in probes}
    for _ in range(REPEATS):
        for key, probe in probes:
            samples[key].append(probe())

    points = []
    for (backend, mode, fused, fanout), vals in samples.items():
        ops_s = statistics.median(vals)
        spread = (max(vals) - min(vals)) / ops_s if ops_s > 0 else 0.0
        capacity, insert_batch, sample_batch = SIZES[backend]
        points.append({
            "backend": backend, "mode": mode, "fused": fused,
            "capacity": capacity, "fanout": fanout,
            "insert_batch": insert_batch, "sample_batch": sample_batch,
            "replay_ops_per_s": round(ops_s, 2),
            "repeats": REPEATS, "rel_spread": round(spread, 4),
        })
        print(f"# replay {backend}/K{fanout}/{mode}/fused={fused}: "
              f"{ops_s:,.0f} ops/s (±{spread:.1%})", file=sys.stderr)
    return points


def compiled_fused_record():
    """Attempt the fused sample+gather kernel *compiled* (non-interpret)
    on this host's default backend and record the outcome.

    Interpret mode inverts the fused kernel's advantage (the committed
    arms above: fused ≈ 4× slower than split on CPU), so the only fair
    measurement is a compiled one.  On TPU this returns a measured
    sample+gather rate, and a kernel that fails there raises; on CPU
    Pallas refuses to lower ("Only interpret mode is supported on CPU
    backend") and the record carries that refusal instead — which is
    why ``ReplayConfig.fused_sample_gather`` defaults to
    backend-appropriate (``tree_ops.default_fused_sample_gather``):
    fused only where it compiles.  The refusal says nothing about the
    chip.
    """
    from repro.core import sumtree
    from repro.kernels import ops as kops
    from repro.kernels import sample_gather as _ksg

    backend = jax.default_backend()
    capacity, _, sample_batch = SIZES["pallas"]
    spec = sumtree.make_spec(capacity, 128)
    key = jax.random.PRNGKey(0)
    tree = sumtree.update(
        spec, sumtree.init(spec),
        jnp.arange(capacity, dtype=jnp.int32),
        jax.random.uniform(key, (capacity,), minval=0.1, maxval=2.0),
        unique=True)
    storage = jax.random.normal(key, (capacity, OBS_DIM))
    bp = ((sample_batch + _ksg.SAMPLE_BLOCK - 1)
          // _ksg.SAMPLE_BLOCK) * _ksg.SAMPLE_BLOCK
    u = jax.random.uniform(jax.random.fold_in(key, 1), (bp,))
    np_ = ((capacity + _ksg.STORAGE_BLOCK - 1)
           // _ksg.STORAGE_BLOCK) * _ksg.STORAGE_BLOCK
    mat = jnp.pad(storage, ((0, np_ - capacity), (0, 0)))
    levels = kops.tree_to_levels(spec, tree)[1:]

    def call(interpret):
        idx, pri, (rows,) = _ksg.sample_gather_levels(
            levels, u, [mat], capacity=spec.capacity, fanout=spec.fanout,
            interpret=interpret)
        jax.block_until_ready(rows)
        return idx, pri, rows

    record = {"attempted_backend": backend}
    try:
        call(interpret=False)           # compile + cold pass
        samples = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            call(interpret=False)
            samples.append(sample_batch / (time.perf_counter() - t0))
        samples.sort()
        record["compiled"] = True
        record["sample_gather_per_s"] = round(samples[len(samples) // 2], 2)
    except Exception as e:  # noqa: BLE001 — the CPU refusal is the result
        if backend == "tpu":
            raise
        record["compiled"] = False
        record["error"] = f"{type(e).__name__}: {e}"[:300]
    return record


def emit_json(out_dir: str, smoke: bool = False) -> str:
    payload = {
        "figure": "replay",
        "metric": "replay_ops_per_s",
        "smoke": smoke,
        # top-level note (schema tolerates extra payload keys): the
        # compiled-vs-interpret resolution of the fused-kernel question
        "fused_compiled": compiled_fused_record(),
        "points": replay_points(smoke=smoke),
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, REPLAY_JSON)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"# wrote {path} ({len(payload['points'])} points)",
          file=sys.stderr)
    return path


def run(csv=True):
    """CSV mode for the benchmarks.run harness."""
    rows = []
    for p in replay_points(smoke=True):
        name = (f"replay/{p['backend']}_K{p['fanout']}_{p['mode']}"
                + ("_fused" if p["fused"] else ""))
        rows.append((name, 1e6 / p["replay_ops_per_s"],
                     p["replay_ops_per_s"]))
    if csv:
        for name, us, derived in rows:
            print(f"{name},{us:.3f},{derived:.2f}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--emit-json", default=None, metavar="DIR")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized iteration budget, same arms")
    args = ap.parse_args()
    if args.emit_json:
        emit_json(args.emit_json, smoke=args.smoke)
    else:
        print("name,us_per_call,derived")
        run()
