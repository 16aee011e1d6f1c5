"""Compiled graphs (ray_tpu/cgraph): compile/execute/teardown/faults.

Covers the ISSUE 4 acceptance surface: the bind-style API, pre-allocated
channel execution (same-node shm and cross-node relay edges), async
execution, error propagation, channel lifecycle (teardown-while-
executing, actor death erroring pending refs, zero PlasmaStore segment
leaks), and double-compile rejection.
"""
import asyncio
import time

import pytest

import ray_tpu
from ray_tpu import exceptions
from ray_tpu.cgraph import InputNode, MultiOutputNode
from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy


@ray_tpu.remote
class Stage:
    def __init__(self, k=1):
        self.k = k

    def add(self, x):
        return x + self.k

    def mul(self, x, factor=2):
        return x * factor

    def pair(self, x):
        return (x, x + self.k)

    def slow(self, x):
        time.sleep(3.0)
        return x

    def boom(self, x):
        raise ValueError("stage exploded")


def _chain(*stages):
    with InputNode() as inp:
        node = inp
        for s in stages:
            node = s.add.bind(node)
    return node


def _compile_chain(*stages, **kw):
    return _chain(*stages).experimental_compile(**kw)


# ---------------------------------------------------------------------------
# compile + execute


def test_compile_and_execute_chain(ray_start_regular):
    a, b, c = Stage.remote(1), Stage.remote(10), Stage.remote(100)
    compiled = _compile_chain(a, b, c)
    try:
        for i in range(5):
            assert compiled.execute(i).get(timeout=30) == i + 111
    finally:
        compiled.teardown()


def test_call_error_mentions_bind(ray_start_regular):
    a = Stage.remote(1)
    with pytest.raises(TypeError, match=r"\.bind\(\)"):
        a.add(1)
    with pytest.raises(TypeError, match=r"\.remote\(\)"):
        a.add(1)


def test_constants_and_kwargs(ray_start_regular):
    a = Stage.remote(5)
    with InputNode() as inp:
        dag = a.mul.bind(inp, factor=3)
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(7).get(timeout=30) == 21
    finally:
        compiled.teardown()


def test_same_actor_local_edge(ray_start_regular):
    a = Stage.remote(1)
    with InputNode() as inp:
        dag = a.add.bind(a.add.bind(a.add.bind(inp)))
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(0).get(timeout=30) == 3
    finally:
        compiled.teardown()


def test_multi_output(ray_start_regular):
    a, b = Stage.remote(1), Stage.remote(10)
    with InputNode() as inp:
        h = a.add.bind(inp)
        dag = MultiOutputNode([a.add.bind(h), b.add.bind(h)])
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(0).get(timeout=30) == [2, 11]
        assert compiled.execute(5).get(timeout=30) == [7, 16]
    finally:
        compiled.teardown()


def test_num_returns_passthrough(ray_start_regular):
    a = Stage.remote(1)
    with InputNode() as inp:
        dag = a.pair.options(num_returns=2).bind(inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(3).get(timeout=30) == (3, 4)
    finally:
        compiled.teardown()
    # mismatched arity surfaces as the stage's TaskError
    with InputNode() as inp:
        dag = a.add.options(num_returns=3).bind(inp)
    compiled = dag.experimental_compile()
    try:
        with pytest.raises(exceptions.TaskError, match="num_returns"):
            compiled.execute(1).get(timeout=30)
    finally:
        compiled.teardown()


def test_concurrency_group_passthrough(ray_start_regular):
    @ray_tpu.remote(concurrency_groups={"io": 2})
    class Grouped:
        def f(self, x):
            return x + 1

    g = Grouped.remote()
    with InputNode() as inp:
        dag = g.f.options(concurrency_group="io").bind(inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(1).get(timeout=30) == 2
    finally:
        compiled.teardown()
    # an undeclared group fails the compile, mirroring .remote() behavior
    with InputNode() as inp:
        dag = g.f.options(concurrency_group="nope").bind(inp)
    with pytest.raises(Exception, match="nope"):
        dag.experimental_compile()


def test_pipelined_execution_ordered_results(ray_start_regular):
    a, b = Stage.remote(1), Stage.remote(10)
    compiled = _compile_chain(a, b)
    try:
        # keep up to pipeline-depth executions in flight
        refs = []
        for i in range(12):
            refs.append((i, compiled.execute(i)))
            if len(refs) >= 2:
                i0, r0 = refs.pop(0)
                assert r0.get(timeout=30) == i0 + 11
        for i0, r0 in refs:
            assert r0.get(timeout=30) == i0 + 11
    finally:
        compiled.teardown()


def test_execute_async(ray_start_regular):
    a, b = Stage.remote(1), Stage.remote(10)
    compiled = _compile_chain(a, b)

    async def drive():
        futs = []
        for i in range(4):
            futs.append(await compiled.execute_async(i))
        return [await f for f in futs]

    try:
        assert asyncio.run(drive()) == [11, 12, 13, 14]
    finally:
        compiled.teardown()


def test_ray_tpu_get_on_cgraph_ref(ray_start_regular):
    a = Stage.remote(1)
    compiled = _compile_chain(a)
    try:
        ref = compiled.execute(41)
        assert ray_tpu.get(ref, timeout=60) == 42
    finally:
        compiled.teardown()


def test_cross_node_edges():
    rt = ray_tpu.init(num_cpus=4, num_nodes=2)
    try:
        nids = list(rt.nodes)
        pins = [NodeAffinitySchedulingStrategy(node_id=n, soft=False)
                for n in nids]
        a = Stage.options(scheduling_strategy=pins[0]).remote(1)
        b = Stage.options(scheduling_strategy=pins[1]).remote(10)
        compiled = _compile_chain(a, b)
        try:
            for i in range(4):
                assert compiled.execute(i).get(timeout=60) == i + 11
        finally:
            compiled.teardown()
    finally:
        ray_tpu.shutdown()


def test_shm_ring_full_capacity_every_slot(ray_start_regular):
    """A payload at the advertised capacity must fit in EVERY ring slot
    — the stride once double-counted the slot's len word, so a
    near-capacity envelope into the last slot overran the segment."""
    from ray_tpu.cgraph.channel import ShmChannel, segment_size
    from ray_tpu.core.ids import ObjectId
    from ray_tpu.core.object_store import SegmentReader

    rt = ray_start_regular
    store = rt.nodes[rt.head_node_id].store
    slots, payload = 4, 64
    cid = ObjectId.from_random()
    size = segment_size(payload, slots)
    name = store.allocate_channel(cid, size)
    reader = SegmentReader()
    try:
        wr = ShmChannel(reader, name, size, edge="t", slots=slots)
        rd = ShmChannel(reader, name, size, edge="t", slots=slots)
        for seq in range(2 * slots + 1):  # wraps the ring twice
            blob = bytes([seq % 251]) * wr.capacity
            wr.send(blob, timeout=5)
            assert rd.recv(timeout=5) == blob, seq
    finally:
        reader.release(name)
        store.release_channel(cid)


def test_queue_channel_reorders_concurrent_deliveries():
    """Cross-node envelopes relay through RPC handler POOLS, so two
    back-to-back sends on one edge can arrive reordered (the pipeline
    engine streams a whole microbatch round down each edge). deliver()
    must hand them to the consumer strictly in seq order."""
    from ray_tpu.cgraph.channel import QueueChannel

    q = QueueChannel("test", edge="t")
    q.deliver(2, b"two")
    q.deliver(0, b"zero")
    q.deliver(1, b"one")
    assert [q.recv(timeout=5) for _ in range(3)] == [b"zero", b"one", b"two"]
    q.deliver(4, b"four")   # gap: held until 3 arrives
    with pytest.raises(exceptions.GetTimeoutError):
        q.recv(timeout=0.1)
    q.deliver(3, b"three")
    assert [q.recv(timeout=5) for _ in range(2)] == [b"three", b"four"]


# ---------------------------------------------------------------------------
# validation + guard rails


def test_compile_requires_one_input(ray_start_regular):
    a = Stage.remote(1)
    with pytest.raises(exceptions.CompiledGraphError, match="InputNode"):
        a.add.bind(0).experimental_compile()
    with pytest.raises(exceptions.CompiledGraphError, match="InputNode"):
        a.mul.bind(InputNode(), factor=InputNode()).experimental_compile()


def test_double_compile_rejected(ray_start_regular):
    a = Stage.remote(1)
    dag = _chain(a)
    compiled = dag.experimental_compile()
    try:
        with pytest.raises(exceptions.CompiledGraphError,
                           match="already compiled"):
            dag.experimental_compile()
    finally:
        compiled.teardown()
    # after teardown the same DAG compiles again
    compiled2 = dag.experimental_compile()
    try:
        assert compiled2.execute(1).get(timeout=30) == 2
    finally:
        compiled2.teardown()


def test_actor_exclusive_to_one_graph(ray_start_regular):
    a = Stage.remote(1)
    compiled = _compile_chain(a)
    try:
        with pytest.raises(exceptions.CompiledGraphError,
                           match="already participates"):
            _compile_chain(a)
    finally:
        compiled.teardown()
    # released on teardown
    compiled2 = _compile_chain(a)
    compiled2.teardown()


def test_max_inflight_guard(ray_start_regular):
    a = Stage.remote(1)
    with InputNode() as inp:
        dag = a.slow.bind(inp)
    compiled = dag.experimental_compile(max_inflight=2)
    try:
        compiled.execute(1)
        compiled.execute(2)
        with pytest.raises(exceptions.CompiledGraphError,
                           match="in flight"):
            compiled.execute(3)
    finally:
        compiled.teardown()


# ---------------------------------------------------------------------------
# error + fault paths


def test_stage_error_propagates_and_graph_survives(ray_start_regular):
    a, b = Stage.remote(1), Stage.remote(10)
    with InputNode() as inp:
        dag = b.add.bind(a.boom.bind(inp))
    compiled = dag.experimental_compile()
    try:
        with pytest.raises(exceptions.TaskError, match="stage exploded"):
            compiled.execute(1).get(timeout=30)
        # the graph keeps running after a stage-level user error
        with pytest.raises(exceptions.TaskError, match="stage exploded"):
            compiled.execute(2).get(timeout=30)
    finally:
        compiled.teardown()
    # and the actors remain usable on the dynamic path
    assert ray_tpu.get(b.add.remote(1), timeout=30) == 11


def test_teardown_while_executing_errors_pending(ray_start_regular):
    a = Stage.remote(1)
    with InputNode() as inp:
        dag = a.slow.bind(inp)
    compiled = dag.experimental_compile()
    ref = compiled.execute(1)
    time.sleep(0.3)  # the stage is now inside the 3s sleep
    compiled.teardown()
    with pytest.raises(exceptions.CompiledGraphClosedError):
        ref.get(timeout=30)
    with pytest.raises(exceptions.CompiledGraphClosedError):
        compiled.execute(2)


def test_actor_death_mid_graph_errors_pending(ray_start_regular):
    a, b = Stage.remote(1), Stage.remote(10)
    with InputNode() as inp:
        dag = b.add.bind(a.slow.bind(inp))
    compiled = dag.experimental_compile()
    ref = compiled.execute(1)
    time.sleep(0.3)
    ray_tpu.kill(a)
    with pytest.raises(exceptions.CompiledGraphClosedError):
        ref.get(timeout=60)
    with pytest.raises(exceptions.CompiledGraphClosedError):
        compiled.execute(2)
    compiled.teardown()  # idempotent after the abort


def test_teardown_releases_segments_no_leak(ray_start_regular):
    rt = ray_start_regular
    node = rt.nodes[rt.head_node_id]
    before = node.store.stats()
    a, b, c = Stage.remote(1), Stage.remote(10), Stage.remote(100)
    compiled = _compile_chain(a, b, c)
    during = node.store.stats()
    assert during["num_channels"] == 4  # in + 2 inter-stage + out
    assert during["used"] > before["used"]
    assert compiled.execute(0).get(timeout=30) == 111
    compiled.teardown()
    after = node.store.stats()
    assert after["num_channels"] == 0
    assert after["used"] == before["used"]
    # actors stay alive and usable after teardown
    assert ray_tpu.get(a.add.remote(1), timeout=30) == 2


def test_teardown_idempotent_and_shutdown_safe(ray_start_regular):
    a = Stage.remote(1)
    compiled = _compile_chain(a)
    assert compiled.execute(1).get(timeout=30) == 2
    compiled.teardown()
    compiled.teardown()  # second call is a no-op


# ---------------------------------------------------------------------------
# observability


def test_cgraph_metrics_emitted(ray_start_regular):
    from ray_tpu.util import metrics

    a, b = Stage.remote(1), Stage.remote(10)
    compiled = _compile_chain(a, b)
    try:
        for i in range(3):
            compiled.execute(i).get(timeout=30)
    finally:
        compiled.teardown()
    body = metrics._render()
    assert "ray_tpu_cgraph_executions_total" in body
    assert "ray_tpu_cgraph_roundtrip_seconds" in body


def test_cgraph_spans_in_timeline(ray_start_regular):
    from ray_tpu.util import tracing

    a, b = Stage.remote(1), Stage.remote(10)
    compiled = _compile_chain(a, b)
    try:
        with tracing.trace("drive") as span:
            compiled.execute(1).get(timeout=30)
        deadline = time.monotonic() + 10
        names = set()
        while time.monotonic() < deadline:
            spans = tracing.get_trace(span.trace_id)
            names = {s.get("name", "") for s in spans}
            if any(n.startswith("cgraph:") for n in names):
                break
            time.sleep(0.2)  # worker span events ship asynchronously
        assert any("add" in n for n in names if n.startswith("cgraph:")), \
            names
    finally:
        compiled.teardown()
