"""Sharded replay buffer on a real (forced 8-device) mesh via shard_map.

Runs in a subprocess because the device count must be set before jax
initializes (the same constraint the dry-run handles); validates the
stratified-sampling + global-IS-weights path end to end."""

import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import ShardedPrioritizedReplay, ShardedReplayConfig

    assert jax.device_count() == 8
    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
    example = {"obs": jnp.zeros((3,), jnp.float32),
               "reward": jnp.zeros((), jnp.float32)}
    rb = ShardedPrioritizedReplay(
        ShardedReplayConfig(capacity_per_shard=64, fanout=8,
                            axis_names=("data",)), example)

    def init_fn():
        return rb.init()

    def insert_fn(state, items):
        return rb.insert(state, items)

    def sample_fn(state, rng):
        idx, items, w = rb.sample(state, rng[0], batch_per_shard=16, beta=1.0)
        pri = rb.local.get_priority(state, idx)
        g_tot, g_cnt = rb.global_stats(state)
        return idx, items, w, pri, g_tot, g_cnt

    def specs_like(shapes):
        # per-shard arrays concat over 'data'; rank-0 scalars (head/count/
        # max_priority) are identical across shards here → replicated spec
        return jax.tree.map(
            lambda s: P("data") if getattr(s, "ndim", 0) > 0 else P(), shapes)

    state_shapes = jax.eval_shape(init_fn)
    state_specs = specs_like(state_shapes)

    with jax.set_mesh(mesh):
        sm_init = jax.shard_map(init_fn, mesh=mesh, in_specs=(),
                            out_specs=state_specs, check_vma=False)
        state = sm_init()
        # per-shard distinct rewards so shards are distinguishable
        items = {
            "obs": jnp.arange(8 * 32 * 3, dtype=jnp.float32).reshape(8 * 32, 3),
            "reward": jnp.repeat(jnp.arange(8, dtype=jnp.float32), 32),
        }
        sm_insert = jax.shard_map(insert_fn, mesh=mesh,
                              in_specs=(state_specs, P("data")),
                              out_specs=state_specs, check_vma=False)
        state = sm_insert(state, items)
        assert int(state.count) == 32  # per-shard count (replicated scalar)

        rngs = jax.random.split(jax.random.PRNGKey(0), 8)
        sm_sample = jax.shard_map(sample_fn, mesh=mesh,
                              in_specs=(state_specs, P("data")),
                              out_specs=(P("data"), P("data"), P("data"),
                                         P("data"), P(), P()),
                              check_vma=False)
        idx, got, w, pri, g_tot, g_cnt = sm_sample(state, rngs)
        # global stats from the psum: full global count across all shards
        np.testing.assert_allclose(float(g_cnt), 256.0)
        assert float(g_tot) > 0
        # stratified locality: each shard sampled its own rewards
        rew = np.asarray(got["reward"]).reshape(8, 16)
        for d in range(8):
            assert (rew[d] == d).all(), (d, rew[d])
        # weights computed against the GLOBAL distribution ∈ (0, 1]
        w_ = np.asarray(w)
        assert (w_ > 0).all() and w_.max() <= 1.0 + 1e-6
        # multi-shard weight parity: every shard normalized by the SAME
        # (pmax'd) global max — recomputing the PER weights from the
        # global stats on the host and dividing by the max over ALL
        # shards' draws must reproduce the shard_map result exactly.
        # (Before the pmax hook each shard divided by its local batch
        # max, an inconsistent per-shard scale factor.)
        pri_ = np.asarray(pri)
        w_ref = (float(g_cnt) * pri_ / float(g_tot)) ** (-1.0)
        w_ref = np.where(pri_ > 0, w_ref, 0.0)
        w_ref = w_ref / w_ref.max()
        np.testing.assert_allclose(w_, w_ref, rtol=1e-5)
        np.testing.assert_allclose(w_.max(), 1.0, rtol=1e-6)
    print("SHARDED_REPLAY_OK")
""")


def test_sharded_replay_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=420, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert "SHARDED_REPLAY_OK" in r.stdout, r.stdout[-800:] + r.stderr[-2000:]


TWO_AXIS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import ShardedPrioritizedReplay, ShardedReplayConfig
    from repro.launch.mesh import pod_data_mesh

    assert jax.device_count() == 4
    mesh = pod_data_mesh(2, 2)
    axes = ("pod", "data")
    example = {"obs": jnp.zeros((3,), jnp.float32),
               "reward": jnp.zeros((), jnp.float32)}
    rb = ShardedPrioritizedReplay(
        ShardedReplayConfig(capacity_per_shard=64, fanout=8,
                            axis_names=axes), example)

    def init_fn():
        return rb.init()

    def insert_fn(state, items):
        return rb.insert(state, items)

    def sample_fn(state, rng):
        idx, items, w = rb.sample(state, rng[0], batch_per_shard=16, beta=1.0)
        pri = rb.local.get_priority(state, idx)
        g_tot, g_cnt = rb.global_stats(state)
        return idx, items, w, pri, g_tot, g_cnt

    def specs_like(shapes):
        return jax.tree.map(
            lambda s: P(axes) if getattr(s, "ndim", 0) > 0 else P(), shapes)

    state_specs = specs_like(jax.eval_shape(init_fn))

    with jax.set_mesh(mesh):
        state = jax.shard_map(init_fn, mesh=mesh, in_specs=(),
                          out_specs=state_specs, check_vma=False)()
        # per-mesh-cell distinct rewards (flattened shard id 0..3) with
        # distinct priority masses per cell, so the global stats are a
        # nontrivial sum over BOTH axes
        items = {
            "obs": jnp.arange(4 * 32 * 3, dtype=jnp.float32).reshape(4 * 32, 3),
            "reward": jnp.repeat(jnp.arange(4, dtype=jnp.float32), 32),
        }
        state = jax.shard_map(insert_fn, mesh=mesh,
                          in_specs=(state_specs, P(axes)),
                          out_specs=state_specs, check_vma=False)(state, items)
        # skew cell 3's priorities upward so the global max normalizer
        # provably comes from a different cell than 0..2 sample locally
        def skew_fn(state):
            sid = jax.lax.axis_index("pod") * 2 + jax.lax.axis_index("data")
            pri = jnp.where(sid == 3, 9.0, 1.0) * jnp.ones((32,))
            return rb.update_priorities(state, jnp.arange(32), pri)
        state = jax.shard_map(skew_fn, mesh=mesh, in_specs=(state_specs,),
                          out_specs=state_specs, check_vma=False)(state)

        rngs = jax.random.split(jax.random.PRNGKey(0), 4)
        idx, got, w, pri, g_tot, g_cnt = jax.shard_map(
            sample_fn, mesh=mesh,
            in_specs=(state_specs, P(axes)),
            out_specs=(P(axes), P(axes), P(axes), P(axes), P(), P()),
            check_vma=False)(state, rngs)

        # global stats psum over BOTH axes: all 4 cells' counts/totals
        np.testing.assert_allclose(float(g_cnt), 128.0)
        # stratified locality: each cell sampled its own rewards
        rew = np.asarray(got["reward"]).reshape(4, 16)
        for d in range(4):
            assert (rew[d] == d).all(), (d, rew[d])
        # IS weights against the GLOBAL two-axis distribution: recompute
        # on the host from the psum'd stats and the pmax'd global max —
        # must match the shard_map result exactly for every cell
        pri_ = np.asarray(pri)
        w_ = np.asarray(w)
        w_ref = (float(g_cnt) * pri_ / float(g_tot)) ** (-1.0)
        w_ref = np.where(pri_ > 0, w_ref, 0.0)
        w_ref = w_ref / w_ref.max()
        np.testing.assert_allclose(w_, w_ref, rtol=1e-5)
        # the max normalizer is global: cells 0..2 (low priority, high
        # weight) dominate, cell 3's draws carry weight < 1
        np.testing.assert_allclose(w_.max(), 1.0, rtol=1e-6)
        assert w_.reshape(4, 16)[3].max() < 0.9
    print("TWO_AXIS_REPLAY_OK")
""")


def test_sharded_replay_two_axis_multidevice():
    """Two-axis ``axis_names=("pod", "data")`` global stats and IS
    weights under a real 2×2 shard_map (the multi-axis loops in
    core/distributed.py, previously untested beyond one axis)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", TWO_AXIS_SCRIPT],
                       capture_output=True, text=True, timeout=420, env=env,
                       cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert "TWO_AXIS_REPLAY_OK" in r.stdout, r.stdout[-800:] + r.stderr[-2000:]
