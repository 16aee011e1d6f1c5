#!/usr/bin/env python
"""CI smoke for the streaming train-feed data plane (ISSUE 19).

Spins up an in-process head plus one REAL remote node agent (a second
OS process over localhost TCP) and drives the whole ingest->train path
on it. Gates:

- a from_numpy -> map_batches(ActorPoolStrategy) plan streams every row
  exactly once through remote preprocessing actors with the BYTE budget
  on (`peak_bytes_inflight` bounded, all blocks emitted)
- one `windowed_shuffle` epoch is a permutation and replays
  bit-identically at the same (seed, epoch)
- `Dataset.split_shards(2)` shards feed a dp=2 `CompiledPipelineEngine`
  via `attach_feed` for 10 steps: the loss trajectory is BIT-IDENTICAL
  to hand-feeding the same shard batches, and the steady-state fed
  steps make ZERO driver dispatches (`runtime.dispatch_counts()`)
- the three data-plane metric families
  (`ray_tpu_data_{bytes_inflight,blocks_emitted_total,
  feed_microbatches_total}`) land in a /metrics render — pump rows ride
  the throttled worker delta path
- engine shutdown returns every store's channel accounting to the
  pre-engine baseline — zero leaked segments on either node
- the timing rows (`data_plane_rows` below) hold their bars:
  `feed_vs_handfed_tokens_ratio` >= 0.95, ingest/shuffle rows non-zero

Exit 0 = healthy; any assertion prints the evidence and exits 1.
Run: python scripts/data_smoke.py   (CI invokes it after trace_smoke)
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

M = 4          # microbatches per replica per step
DP = 2
MB_SIZE = 2
WIDTH = 16
STEPS = 10


def _stage(width: int):
    import jax
    import jax.numpy as jnp

    k = jax.random.PRNGKey(3)

    def fn(p, x, targets):
        return jnp.mean((x @ p["w"] + p["b"] - targets) ** 2)

    param = {"w": jax.random.normal(k, (width, width)) * 0.3,
             "b": jnp.zeros((width,))}
    return [fn], [param]


def _pipeline_mlp(num_chunks: int, width: int, M: int, mb_size: int):
    """Compute-light tanh-MLP pipeline: chunk fns, their params, M
    microbatches and targets."""
    import jax
    import jax.numpy as jnp

    k = jax.random.PRNGKey(0)

    def mk_mid():
        def fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])
        return fn

    def mk_last():
        def fn(p, x, targets):
            return jnp.mean((x @ p["w"] + p["b"] - targets) ** 2)
        return fn

    fns = [mk_mid() for _ in range(num_chunks - 1)] + [mk_last()]
    params = [
        {"w": jax.random.normal(jax.random.fold_in(k, i),
                                (width, width)) * 0.3,
         "b": jnp.zeros((width,))}
        for i in range(num_chunks)]
    xs = jax.random.normal(jax.random.fold_in(k, 91), (M * mb_size, width))
    ys = jax.random.normal(jax.random.fold_in(k, 92), (M * mb_size, width))
    mbs = [xs[i * mb_size:(i + 1) * mb_size] for i in range(M)]
    tgts = [ys[i * mb_size:(i + 1) * mb_size] for i in range(M)]
    return fns, params, mbs, tgts


def data_plane_rows() -> dict:
    """Timing rows of the streaming data plane (docs/DATA.md), on the
    CPU at smoke sizes. Assumes an initialized cluster.

    - ``data_ingest_mb_s``: MB/s through a from_numpy->map_batches
      streaming plan with the byte budget ON (~8 blocks worth), wall
      clock over the block bytes drained at the consumer.
    - ``shuffle_epoch_ms``: wall clock to drain one ``windowed_shuffle``
      epoch end-to-end on the same block population — the streaming-
      shuffle latency a training epoch pays.
    - ``feed_vs_handfed_tokens_ratio``: steady-state step time of a
      hand-fed ``CompiledPipelineEngine`` over the SAME engine config
      fed the identical microbatches through ``attach_feed`` pump
      actors. >= 0.95 is the bar ``main`` holds it to: the pump tier
      must keep the rings at least as resident as the driver's
      synchronous sends.
    """
    import numpy as np
    import optax

    import ray_tpu.data as rd
    from ray_tpu.data import DataContext, DataFeed
    from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

    out: dict = {}

    # -- ingest MB/s, byte budget on --------------------------------------
    rows, width, P = 4096, 64, 8
    x = np.random.default_rng(0).standard_normal(
        (rows, width)).astype(np.float32)
    ctx = DataContext.get_current()
    old_budget = ctx.target_max_bytes_inflight
    ctx.target_max_bytes_inflight = 8 * (x.nbytes // P)
    try:
        t0 = time.perf_counter()
        ds = rd.from_numpy({"x": x}, parallelism=P).map_batches(
            lambda b: {"x": np.tanh(b["x"])})
        total = 0
        for b in ds.iter_batches(batch_size=None):
            total += b["x"].nbytes
        dt = time.perf_counter() - t0
    finally:
        ctx.target_max_bytes_inflight = old_budget
    assert total == x.nbytes, f"drained {total} of {x.nbytes} bytes"
    out["data_ingest_mb_s"] = round(total / dt / 1e6, 1)
    out["data_ingest_blocks"] = P
    out["data_ingest_peak_bytes_inflight"] = \
        ds.stats().get("peak_bytes_inflight", 0)

    # -- windowed-shuffle epoch drain -------------------------------------
    t0 = time.perf_counter()
    sds = rd.from_numpy({"x": x}, parallelism=P).windowed_shuffle(
        window_blocks=4, seed=11)
    n = 0
    for b in sds.iter_batches(batch_size=None):
        n += len(b["x"])
    out["shuffle_epoch_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    assert n == rows, f"shuffle epoch drained {n} of {rows} rows"

    # -- feed-fed vs hand-fed engine throughput ---------------------------
    # compute-meaningful microbatches (64 rows x 128 wide) so the row
    # measures starvation, not channel-poll jitter; MEDIAN step time on
    # both sides for the same reason (CI runs on oversubscribed cores)
    M = 4
    warmup, timed = 2, 6
    fns, params, mbs, tgts = _pipeline_mlp(2, 128, M, mb_size=64)
    tx = optax.sgd(1e-2)

    def _median_steps(eng, step):
        for _ in range(warmup):
            step()
        ts = []
        for _ in range(timed):
            t0 = time.perf_counter()
            step()
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=M,
                                 channel_bytes=1 << 20)
    try:
        hand_s = _median_steps(eng, lambda: eng.step(mbs, tgts))
    finally:
        eng.shutdown()

    nmbs = [np.asarray(v) for v in mbs]
    ntgts = [np.asarray(v) for v in tgts]
    steps_total = warmup + timed + 4

    def factory():
        def it():
            for _ in range(steps_total):
                for xx, tt in zip(nmbs, ntgts):
                    yield xx, tt
        return it()

    feng = CompiledPipelineEngine(fns, params, tx, num_microbatches=M,
                                  channel_bytes=1 << 20)
    try:
        feng.attach_feed(DataFeed([factory]))
        fed_s = _median_steps(feng, lambda: feng.step())
    finally:
        feng.shutdown()
    tokens_per_step = M * nmbs[0].shape[0]
    out["data_handfed_tokens_per_s"] = round(tokens_per_step / hand_s, 1)
    out["data_fed_tokens_per_s"] = round(tokens_per_step / fed_s, 1)
    out["feed_vs_handfed_tokens_ratio"] = round(hand_s / fed_s, 3)
    return out


def main() -> int:
    import numpy as np
    import optax

    import ray_tpu  # noqa: F401 — Cluster below owns init
    import ray_tpu.data as rd
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.runtime import dispatch_counts
    from ray_tpu.data import ActorPoolStrategy, DataContext, DataFeed
    from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine
    from ray_tpu.util import metrics

    c = Cluster(head_resources={"CPU": 4.0})
    try:
        c.add_remote_node(num_cpus=4.0)

        def store_channels() -> dict:
            return {nid: n.store.stats().get("num_channels", 0)
                    for nid, n in c.runtime.nodes.items()}

        baseline = store_channels()

        # 1) byte-budgeted ingest through remote preprocessing actors:
        # every row exactly once (in order — preserve_order default),
        # peak outstanding bytes bounded. 256 KiB blocks so the
        # store-reported sizes dominate the 64 KiB bootstrap estimate.
        rng = np.random.default_rng(0)
        big = rng.standard_normal((8 * 1024, 64)).astype(np.float32)
        block_bytes = big.nbytes // 8
        ctx = DataContext.get_current()
        old_budget = ctx.target_max_bytes_inflight
        ctx.target_max_bytes_inflight = 3 * block_bytes
        try:
            ds = rd.from_numpy({"x": big}, parallelism=8).map_batches(
                lambda b: {"x": np.tanh(b["x"]).astype(np.float32)},
                compute=ActorPoolStrategy(2))
            got = np.concatenate(
                [b["x"] for b in ds.iter_batches(batch_size=None)])
        finally:
            ctx.target_max_bytes_inflight = old_budget
        expect = np.tanh(big).astype(np.float32)
        assert got.shape == expect.shape and np.array_equal(got, expect), \
            "preprocessed stream is not the input rows in order"
        st = ds.stats()
        # read segment + actor-pool segment both emit -> 16 block emits
        assert st["blocks_emitted"] >= 16, st
        # two windows at ~3-4 blocks each; full materialization (16
        # blocks across both generations) must never be reached
        assert 0 < st["peak_bytes_inflight"] <= 10 * block_bytes, st
        print(f"byte-budgeted ingest OK ({st['blocks_emitted']} block "
              f"emits, peak {st['peak_bytes_inflight']} bytes)")

        # 2) windowed shuffle: one epoch is a permutation; same
        # (seed, epoch) replays bit-identically
        rows = 256
        base = rd.from_numpy({"x": np.arange(rows, dtype=np.int64)},
                             parallelism=8)
        sh = base.windowed_shuffle(window_blocks=4, seed=11)

        def drain():
            return np.concatenate(
                [b["x"] for b in sh.iter_batches(batch_size=None)])

        e0, e0b = drain(), drain()
        assert np.array_equal(np.sort(e0), np.arange(rows)), \
            "shuffle epoch is not a permutation"
        assert not np.array_equal(e0, np.arange(rows)), \
            "shuffle did not move any row"
        assert np.array_equal(e0, e0b), \
            "same (seed, epoch) must replay bit-identically"
        print("windowed shuffle OK (permutation, deterministic replay)")

        # 3) dp=2 engine fed via attach_feed from split_shards(2):
        # 10 fed steps, loss bit-identical to hand-feeding the same
        # shard batches, zero driver dispatches in steady state
        w_true = rng.standard_normal((WIDTH, WIDTH)).astype(np.float32) * 0.5
        # DP*M blocks of MB_SIZE rows: each block becomes exactly one
        # microbatch, each shard exactly M of them
        raw = rng.standard_normal(
            (DP * M * MB_SIZE, WIDTH)).astype(np.float32)
        feed_ds = rd.from_numpy({"x": raw}, parallelism=DP * M).map_batches(
            lambda b: {"x": np.tanh(b["x"]).astype(np.float32)},
            compute=ActorPoolStrategy(2))
        shards = feed_ds.split_shards(DP)

        def to_microbatches(shard, steps=STEPS + 1, w=w_true):
            def it():
                for _ in range(steps):
                    for b in shard.iter_batches(batch_size=MB_SIZE):
                        x = b["x"]
                        yield x, np.tanh(x @ w)
            return it()

        # the hand-fed reference consumes the SAME DataShard objects
        # driver-side, so the replayed arrays are bitwise the feed's
        mbs, tgts = [], []
        for shard in shards:
            for b in shard.iter_batches(batch_size=MB_SIZE):
                mbs.append(b["x"])
                tgts.append(np.tanh(b["x"] @ w_true))
        assert len(mbs) == DP * M, f"sharding produced {len(mbs)} mbs"

        fns, params = _stage(WIDTH)
        tx = optax.adam(1e-2)
        ref = CompiledPipelineEngine(fns, params, tx, num_microbatches=M,
                                     dp=DP, channel_bytes=1 << 18)
        try:
            ref_losses = [ref.step(mbs, tgts) for _ in range(STEPS)]
        finally:
            ref.shutdown()

        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=M,
                                     dp=DP, channel_bytes=1 << 18)
        try:
            eng.attach_feed(DataFeed.from_shards(shards, to_microbatches))
            losses = [eng.step()]
            d0, r0 = dispatch_counts()
            losses += [eng.step() for _ in range(STEPS - 1)]
            d1, r1 = dispatch_counts()
            assert losses == ref_losses, \
                f"fed != hand-fed: {losses} vs {ref_losses}"
            assert (d1 - d0, r1 - r0) == (0, 0), \
                f"steady-state fed steps dispatched ({d1 - d0}, {r1 - r0})"
            fst = eng.feed_stats()
            assert all(s["error"] is None for s in fst), fst
            assert all(s["sent"] >= STEPS * M for s in fst), fst
            print(f"fed dp=2 engine OK ({STEPS} steps bit-identical, "
                  f"0 driver dispatches, "
                  f"pumps sent {[s['sent'] for s in fst]})")

            # 4) the three data-plane metric families are scraped
            deadline = time.monotonic() + 15
            want = ("ray_tpu_data_bytes_inflight",
                    "ray_tpu_data_blocks_emitted_total",
                    "ray_tpu_data_feed_microbatches_total")
            body = metrics._render()
            while (not all(w in body for w in want)
                   and time.monotonic() < deadline):
                time.sleep(0.3)
                body = metrics._render()
            missing = [w for w in want if w not in body]
            assert not missing, f"missing metrics: {missing}"
            print("data metrics OK")
        finally:
            eng.shutdown()

        # 5) teardown leaked nothing on either node
        after = store_channels()
        assert after == baseline, \
            f"leaked channels: baseline={baseline} after={after}"
        print("shutdown channel accounting OK")
    finally:
        c.shutdown()

    # 6) timing rows hold their bars (docs/DATA.md methodology) — on a
    # fresh single-node runtime; best-of-2 on the ratio: it is a timing
    # row and CI cores are oversubscribed, but a starving pump tier
    # fails BOTH attempts
    import ray_tpu

    ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))
    try:
        rows_out = data_plane_rows()
        ratio = rows_out["feed_vs_handfed_tokens_ratio"]
        if ratio < 0.95:
            print(f"ratio {ratio} < 0.95, retrying once: {rows_out}")
            rows_out = data_plane_rows()
            ratio = max(ratio, rows_out["feed_vs_handfed_tokens_ratio"])
        assert ratio >= 0.95, \
            f"feed_vs_handfed_tokens_ratio {ratio} < 0.95: {rows_out}"
        assert rows_out["data_ingest_mb_s"] > 0, rows_out
        assert rows_out["shuffle_epoch_ms"] > 0, rows_out
        print(f"timing rows OK (ratio {ratio}, "
              f"ingest {rows_out['data_ingest_mb_s']} MB/s, "
              f"shuffle {rows_out['shuffle_epoch_ms']} ms)")
    finally:
        ray_tpu.shutdown()
    print("data smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
