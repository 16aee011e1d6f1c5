"""Chaos engine (ray_tpu/chaos) + util/retry policy + teardown
idempotency under races.

ISSUE 10 acceptance surface: plans parse from the RAY_TPU_CHAOS spec,
every probabilistic draw replays deterministically from the seed, frame
injection (drop/delay/dup) really perturbs a live RPC channel without
breaking the request plane, injected pull failures ride the existing
retry loop to success, kill schedules fire on time against the runtime,
hooks cost nothing when disabled, and shutdown/teardown paths survive
concurrent + reentrant double-invocation.
"""
import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu import chaos
from ray_tpu.util.retry import RetryError, RetryPolicy, call_with_retry


@pytest.fixture(autouse=True)
def _chaos_off():
    yield
    chaos.disable()


# ---------------------------------------------------------------------------
# retry policy (util/retry.py)
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def test_backoff_curve_and_ceiling(self):
        p = RetryPolicy(initial_backoff_s=0.1, multiplier=2.0,
                        max_backoff_s=0.5, jitter=0.0)
        assert [p.backoff(i) for i in range(4)] == [0.1, 0.2, 0.4, 0.5]

    def test_jitter_bounds(self):
        p = RetryPolicy(initial_backoff_s=0.1, multiplier=1.0,
                        max_backoff_s=1.0, jitter=0.5)
        for _ in range(50):
            assert 0.05 <= p.backoff(0) <= 0.15

    def test_max_attempts_budget(self):
        p = RetryPolicy(initial_backoff_s=0.0, jitter=0.0, max_attempts=3)
        assert list(p.sleeps()) == [0, 1, 2]

    def test_deadline_budget(self):
        p = RetryPolicy(initial_backoff_s=0.05, multiplier=1.0,
                        jitter=0.0, deadline_s=0.12)
        t0 = time.monotonic()
        attempts = list(p.sleeps())
        assert len(attempts) >= 2
        assert time.monotonic() - t0 < 1.0

    def test_interrupt_stops_sleeping(self):
        ev = threading.Event()
        ev.set()
        p = RetryPolicy(initial_backoff_s=10.0, max_attempts=5)
        assert list(p.sleeps(interrupt=ev)) == []

    def test_call_with_retry_succeeds_after_failures(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "ok"

        out = call_with_retry(
            flaky, policy=RetryPolicy(initial_backoff_s=0.001,
                                      jitter=0.0, max_attempts=5),
            retry_on=(OSError,))
        assert out == "ok" and calls["n"] == 3

    def test_call_with_retry_exhausts_typed(self):
        def always():
            raise OSError("down")

        with pytest.raises(RetryError) as ei:
            call_with_retry(
                always, policy=RetryPolicy(initial_backoff_s=0.001,
                                           jitter=0.0, max_attempts=3),
                retry_on=(OSError,), description="probe")
        assert ei.value.attempts == 3
        assert isinstance(ei.value.last, OSError)

    def test_unlisted_error_propagates_immediately(self):
        def boom():
            raise ValueError("logic bug")

        with pytest.raises(ValueError):
            call_with_retry(boom, policy=RetryPolicy(max_attempts=10),
                            retry_on=(OSError,))


# ---------------------------------------------------------------------------
# plan parsing + determinism
# ---------------------------------------------------------------------------


class TestChaosPlan:
    def test_parse_full_spec(self):
        p = chaos.ChaosPlan.parse(
            "seed=42; rpc_drop=0.05:direct_result; rpc_delay=0.1@0.02;"
            "pull_fail=0.2; kill=actor:trainer@5.0; kill=worker@7.5")
        assert p.seed == 42
        kinds = {r.kind: r for r in p.rules}
        assert kinds["rpc_drop"].prob == 0.05
        assert kinds["rpc_drop"].match == "direct_result"
        assert kinds["rpc_delay"].param == 0.02
        assert [(k.target, k.at_s) for k in p.kills] == [
            ("actor:trainer", 5.0), ("worker", 7.5)]

    def test_parse_rejects_unknown_entry(self):
        with pytest.raises(ValueError, match="unknown chaos spec"):
            chaos.ChaosPlan.parse("frobnicate=1")

    def test_draws_replay_bit_identical(self):
        spec = "seed=9;recv_drop=0.3;pull_fail=0.5"
        e1 = chaos.ChaosEngine(chaos.ChaosPlan.parse(spec))
        e2 = chaos.ChaosEngine(chaos.ChaosPlan.parse(spec))
        s1 = [(e1.recv_drop("m"), e1.pull_fail("x")) for _ in range(100)]
        s2 = [(e2.recv_drop("m"), e2.pull_fail("x")) for _ in range(100)]
        assert s1 == s2
        assert any(a for a, _ in s1) and any(b for _, b in s1)

    def test_points_draw_independently(self):
        """Interleaving one point's draws must not shift another's —
        per-point RNGs are what make a multi-threaded run replayable."""
        spec = "seed=3;recv_drop=0.4;pull_fail=0.4"
        e1 = chaos.ChaosEngine(chaos.ChaosPlan.parse(spec))
        e2 = chaos.ChaosEngine(chaos.ChaosPlan.parse(spec))
        drops1 = [e1.recv_drop("m") for _ in range(40)]
        # e2 interleaves pull draws between every drop draw
        drops2 = []
        for _ in range(40):
            e2.pull_fail("x")
            drops2.append(e2.recv_drop("m"))
        assert drops1 == drops2

    def test_match_filter(self):
        e = chaos.ChaosEngine(chaos.ChaosPlan.parse(
            "seed=1;recv_drop=1.0:heartbeat"))
        assert not e.recv_drop("task_done")
        assert e.recv_drop("heartbeat")

    def test_env_plan(self, monkeypatch):
        monkeypatch.setenv(chaos.ENV_VAR, "seed=5;rpc_drop=0.1")
        p = chaos.plan_from_env()
        assert p is not None and p.seed == 5
        monkeypatch.delenv(chaos.ENV_VAR)
        assert chaos.plan_from_env() is None


# ---------------------------------------------------------------------------
# live injection
# ---------------------------------------------------------------------------


class TestLiveInjection:
    def test_zero_overhead_hooks_absent_when_disabled(self):
        import ray_tpu.cgraph.channel as channel_mod
        import ray_tpu.core.rpc as rpc_mod
        import ray_tpu.core.runtime as runtime_mod

        assert rpc_mod._CHAOS is None
        assert runtime_mod._CHAOS is None
        assert channel_mod._CHAOS is None

    def test_oneway_drop_spares_request_plane(self):
        """drop=1.0 on a matching method kills every such oneway frame,
        while request/response frames (and unmatched oneways) flow."""
        from ray_tpu.core import rpc as rpc_mod

        got = []

        def handler_factory(ch):
            def handler(method, payload):
                got.append((method, payload))
                return ("pong", payload)

            return handler

        srv = rpc_mod.RpcServer(("127.0.0.1", 0), handler_factory,
                                family="AF_INET")
        ch = rpc_mod.connect(srv.address, name="t")
        try:
            eng = chaos.enable("seed=1;rpc_drop=1.0:doomed")
            ch.notify("doomed", 1)
            ch.notify("survives", 2)
            assert ch.call("req", 3, timeout=10) == ("pong", 3)
            deadline = time.monotonic() + 5
            while len(got) < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            methods = [m for m, _ in got]
            assert "survives" in methods and "req" in methods
            assert "doomed" not in methods
            assert eng.injected.get("rpc_drop", 0) >= 1
        finally:
            chaos.disable()
            ch.close()
            srv.close()

    def test_duplicate_oneway_delivered_twice(self):
        from ray_tpu.core import rpc as rpc_mod

        got = []

        def handler_factory(ch):
            def handler(method, payload):
                got.append(payload)

            return handler

        srv = rpc_mod.RpcServer(("127.0.0.1", 0), handler_factory,
                                family="AF_INET")
        ch = rpc_mod.connect(srv.address, name="t")
        try:
            chaos.enable("seed=1;rpc_dup=1.0:dup_me")
            ch.notify("dup_me", 7)
            deadline = time.monotonic() + 5
            while len(got) < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert got == [7, 7]
        finally:
            chaos.disable()
            ch.close()
            srv.close()

    def test_injected_pull_failures_ride_retry_to_success(self):
        """pull_fail < 1.0 makes remote fetches fail transiently; the
        fetch_one retry loop (now on the shared RetryPolicy backoff)
        must still land the object."""
        from ray_tpu.cluster_utils import Cluster

        c = Cluster(head_resources={"CPU": 2.0})
        try:
            remote = c.add_remote_node(num_cpus=2.0)
            from ray_tpu.util.scheduling_strategies import (
                NodeAffinitySchedulingStrategy)

            @ray_tpu.remote(scheduling_strategy=
                            NodeAffinitySchedulingStrategy(
                                remote.node_id, soft=False))
            def big():
                return os.urandom(300_000)  # > inline ceiling: store path

            eng = chaos.enable("seed=11;pull_fail=0.6")
            vals = [ray_tpu.get(big.remote(), timeout=120)
                    for _ in range(4)]
            assert all(len(v) == 300_000 for v in vals)
            assert eng.injected.get("pull_fail", 0) >= 1
        finally:
            chaos.disable()
            c.shutdown()

    def test_kill_schedule_fires_and_actor_restarts(self, ray_start_regular):
        @ray_tpu.remote(max_restarts=2)
        class Victim:
            def ping(self):
                return os.getpid()

        a = Victim.options(name="victim").remote()
        first = ray_tpu.get(a.ping.remote(), timeout=30)
        eng = chaos.enable("seed=2;kill=actor:victim@0.3",
                           runtime=ray_start_regular)
        deadline = time.monotonic() + 30
        while eng.injected.get("kill", 0) < 1:
            assert time.monotonic() < deadline, "kill never fired"
            time.sleep(0.05)
        # restartable actor comes back; calls succeed again
        deadline = time.monotonic() + 60
        while True:
            try:
                second = ray_tpu.get(a.ping.remote(), timeout=15)
                break
            except Exception:
                assert time.monotonic() < deadline
                time.sleep(0.2)
        assert second != first

    def test_channel_poison_surfaces_typed_error(self, ray_start_regular):
        """A poisoned cgraph channel aborts the graph with the typed
        closed error — never a hang or corrupted result."""
        from ray_tpu import exceptions

        @ray_tpu.remote
        class Echo:
            def fwd(self, x):
                return x + 1

        a = Echo.remote()
        with ray_tpu.InputNode() as inp:
            dag = a.fwd.bind(inp)
        compiled = dag.experimental_compile()
        try:
            assert ray_tpu.get(compiled.execute(1), timeout=60) == 2
            chaos.enable("seed=1;channel_poison=1.0")
            with pytest.raises(exceptions.CompiledGraphError):
                compiled.execute(2).get(timeout=30)
        finally:
            chaos.disable()
            compiled.teardown()


# ---------------------------------------------------------------------------
# shutdown/teardown idempotency under double-invocation (ISSUE 10
# satellite: signal handlers + atexit races)
# ---------------------------------------------------------------------------


class TestTeardownIdempotency:
    def test_runtime_shutdown_concurrent_and_reentrant(self):
        rt = ray_tpu.init(num_cpus=2)
        errs = []

        def hammer():
            try:
                rt.shutdown()
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        rt.shutdown()  # and from this thread too
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), "shutdown hung"
        assert not errs, errs
        rt.shutdown()  # post-completion call still a no-op
        from ray_tpu.core import runtime as runtime_mod

        runtime_mod.set_runtime(None)

    def test_compiled_dag_concurrent_teardown(self, ray_start_regular):
        rt = ray_start_regular
        node = rt.nodes[rt.head_node_id]
        before = node.store.stats()["num_channels"]

        @ray_tpu.remote
        class S:
            def f(self, x):
                return x

        a = S.remote()
        with ray_tpu.InputNode() as inp:
            dag = a.f.bind(inp)
        compiled = dag.experimental_compile()
        assert ray_tpu.get(compiled.execute(5), timeout=60) == 5
        errs = []

        def tear():
            try:
                compiled.teardown()
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        threads = [threading.Thread(target=tear) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        assert not errs, errs
        # every waiter returned only after the segments were released
        assert node.store.stats()["num_channels"] == before

    def test_compiled_pipeline_concurrent_shutdown(self, ray_start_regular):
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        import jax
        import jax.numpy as jnp

        k = jax.random.PRNGKey(0)

        def mk_mid():
            def fn(p, x):
                return jnp.tanh(x @ p["w"])

            return fn

        def mk_last():
            def fn(p, x, t):
                return jnp.mean((x @ p["w"] - t) ** 2)

            return fn

        params = [{"w": jax.random.normal(jax.random.fold_in(k, i),
                                          (4, 4))} for i in range(2)]
        xs = jax.random.normal(jax.random.fold_in(k, 7), (4, 4))
        eng = CompiledPipelineEngine(
            [mk_mid(), mk_last()], params, optax.sgd(0.1),
            num_microbatches=2, channel_bytes=1 << 18)
        eng.step([xs[:2], xs[2:]], [xs[:2], xs[2:]])
        errs = []

        def down():
            try:
                eng.shutdown()
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        threads = [threading.Thread(target=down) for _ in range(3)]
        for t in threads:
            t.start()
        eng.shutdown()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), "shutdown hung"
        assert not errs, errs
