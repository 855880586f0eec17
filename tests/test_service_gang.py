"""Replay-service gang (launch/multiprocess.py + service/, DESIGN.md
§11): real OS processes — 1 replay server + 2 actor writers + 1 learner
— train CartPole end-to-end through the TCP service boundary.

These are the slowest tier-1 tests alongside test_multiprocess.py (every
role imports jax in its own process); the replay-service-smoke CI job
runs the same gang shape.  What they pin down:

  * the decoupled gang *learns*: the near-greedy eval return of the
    learner's final params clears the same criterion as the in-process
    system test (mean return > 30, tests/test_system.py);
  * the rate limiter's band theorem holds across process boundaries:
    |realized_spi − configured_spi| ≤ error_buffer / (inserts − min);
  * the learner can exit mid-run and a fresh process resumes from the
    checkpoint (CheckpointManager + elastic reshard) against the
    still-live service — actors park in writer backpressure, nothing
    deadlocks, and the learn-step count continues where it stopped.
"""

import pytest

from repro.launch import multiprocess as mp

# the proven in-process hyperparameters of tests/test_system.py, recast
# as explicit flow control: 1400 learns of batch 64 over ~11200 env
# steps ⇒ samples_per_insert = learns·batch/steps = 8
GANG = dict(n_actors=2, samples_per_insert=8.0, batch_size=64,
            warmup=400, n_envs=8, actor_chunk=8, epsilon=0.2, seed=1)


def _assert_spi_band(kv):
    realized = float(kv["REALIZED_SPI"])
    configured = float(kv["CONFIGURED_SPI"])
    tol = float(kv["SPI_TOLERANCE"])
    assert abs(realized - configured) <= tol, (realized, configured, tol)


def test_service_gang_trains_cartpole():
    res = mp.launch_service(learn_steps=1400, timeout_s=540.0, **GANG)

    server, learner = res["server"], res["learner"]
    _assert_spi_band(server)
    # every role names its platform; the host roles are pinned to the CPU
    # and the learner takes the default platform (the CPU here)
    assert {kv["PLATFORM"] for kv in res.values()} == {"cpu"}
    # counters agree across the boundary: the server's limiter totals are
    # what the learner saw in its final stats round trip
    assert server["INSERTS"] == learner["SERVICE_INSERTS"]
    assert server["SAMPLES"] == learner["SERVICE_SAMPLES"]
    assert int(learner["LEARN_STEPS"]) == 1400
    assert int(server["SAMPLES"]) == 1400 * GANG["batch_size"]
    # every transition the actors shipped landed in the (single) shard;
    # the server may hold up to one extra in-flight chunk per actor
    # (admitted between the learner's stop and the actor observing it)
    appended = sum(int(res[f"actor-{a}"]["TRANSITIONS"])
                   for a in range(GANG["n_actors"]))
    burst = GANG["actor_chunk"] * GANG["n_envs"]
    inserts = int(server["INSERTS"])
    assert appended <= inserts <= appended + GANG["n_actors"] * burst
    assert int(server["PER_SHARD_COUNT"]) == inserts
    # both writers made real progress (no actor starved by backpressure)
    for a in range(GANG["n_actors"]):
        assert int(res[f"actor-{a}"]["CHUNKS"]) > 10, res[f"actor-{a}"]
        assert int(res[f"actor-{a}"]["PARAMS_VERSION"]) > 1
    # the learning criterion of tests/test_system.py, through the service
    assert float(learner["EVAL_RETURN"]) > 30.0, learner


def test_service_gang_learner_restart_resumes_from_checkpoint(tmp_path):
    res = mp.launch_service(learn_steps=800, timeout_s=540.0,
                            ckpt_dir=str(tmp_path), ckpt_every=100,
                            restart_learner_after=300, **GANG)

    first, resumed = res["learner-0"], res["learner"]
    assert first["EXITED_EARLY"] == "1"
    assert int(first["LEARN_STEPS"]) == 300
    assert int(resumed["RESUMED_FROM"]) == 300
    assert int(resumed["LEARN_STEPS"]) == 800
    # the service survived the learner gap: one continuous limiter
    # history, still inside the band, with both actors running throughout
    _assert_spi_band(res["server"])
    assert int(res["server"]["SAMPLES"]) == 800 * GANG["batch_size"]
    for a in range(GANG["n_actors"]):
        assert int(res[f"actor-{a}"]["CHUNKS"]) > 10, res[f"actor-{a}"]


def test_service_gang_server_restart_restores_from_snapshot(tmp_path):
    """The server is the casualty (DESIGN.md §14): a fault plan hard-kills
    it at its 40th append, actors and learner park in reconnect backoff,
    a replacement restores the per-append shard snapshot onto the same
    port, and training runs through the fault to the same learning
    criterion.  Exactly-once is asserted as *bit-identical counters*:
    every actor's acked-append count equals the restored server's
    per-writer applied table — zero duplicate inserts across the crash."""
    res = mp.launch_service(learn_steps=1400, timeout_s=600.0,
                            snapshot_dir=str(tmp_path),
                            snapshot_every_appends=1,
                            restart_server_after=40,
                            retry_deadline=240.0, **GANG)

    server, learner = res["server"], res["learner"]
    assert int(server["RESTORED_STEP"]) >= 1
    assert int(server["SNAPSHOTS"]) >= 1
    # per-writer exactly-once across the restart: the client-side ack
    # count IS the server-side applied count, for every actor
    applied = dict(kv.split(":") for kv in
                   server["WRITER_APPENDS"].split(","))
    for a in range(GANG["n_actors"]):
        actor = res[f"actor-{a}"]
        assert int(actor["ACKED_APPENDS"]) == int(applied[f"actor-{a}"]), (
            actor, server)
        # the fault really hit this writer's connection
        assert int(actor["RECONNECTS"]) >= 1, actor
    # duplicates were *detected* (and not applied); the server may have
    # lost pre-crash dedup-ack counts that clients kept, never the
    # reverse
    deduped = sum(int(res[f"actor-{a}"]["DEDUPED_APPENDS"])
                  for a in range(GANG["n_actors"]))
    assert int(server["DUP_APPENDS"]) <= deduped
    # one continuous limiter history through the crash, inside the band
    _assert_spi_band(server)
    assert int(learner["LEARN_STEPS"]) == 1400
    # the learning criterion of tests/test_system.py, through the fault
    assert float(learner["EVAL_RETURN"]) > 30.0, learner


def test_launch_service_validates_inputs():
    with pytest.raises(ValueError, match="n_actors"):
        mp.launch_service(n_actors=0)
    with pytest.raises(ValueError, match="restart_learner_after"):
        mp.launch_service(n_actors=1, restart_learner_after=10)
    with pytest.raises(ValueError, match="restart_server_after"):
        mp.launch_service(n_actors=1, restart_server_after=10)
