"""Train layer tests: JaxTrainer end-to-end on the virtual mesh, reporting,
checkpointing, failure restart (mirrors ref: python/ray/train/tests/
test_backend.py, test_data_parallel_trainer.py)."""
import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (Checkpoint, FailureConfig, JaxTrainer, Result,
                           RunConfig, ScalingConfig)


@pytest.fixture
def rt(tmp_path):
    runtime = ray_tpu.init(num_cpus=8)
    yield runtime
    ray_tpu.shutdown()


def test_basic_fit_reports_and_checkpoints(rt, tmp_path):
    def loop(config):
        ctx = train.get_context()
        for i in range(3):
            ckpt = None
            if ctx.get_world_rank() == 0:
                ckpt = Checkpoint.from_dict({"step": i, "w": np.ones(4) * i})
            train.report({"loss": 1.0 / (i + 1), "rank": ctx.get_world_rank()},
                         checkpoint=ckpt)

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2, devices_per_worker=4),
        run_config=RunConfig(name="t1", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert len(result.metrics_history) == 3
    assert result.metrics["loss"] == pytest.approx(1.0 / 3)
    data = result.checkpoint.to_dict()
    assert data["step"] == 2
    np.testing.assert_allclose(data["w"], 2.0)
    assert os.path.isdir(os.path.join(str(tmp_path), "t1"))


def test_mesh_available_in_loop(rt, tmp_path):
    def loop(config):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = train.get_mesh()
        x = jnp.arange(8.0)
        y = jax.jit(lambda x: (x * 2).sum(),
                    in_shardings=NamedSharding(mesh, P("dp")))(x)
        train.report({"total": float(y), "devices": len(mesh.devices.flat)})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2, devices_per_worker=4),
        run_config=RunConfig(name="t2", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["total"] == 56.0
    assert result.metrics["devices"] == 4


def test_dataset_shards(rt, tmp_path):
    def loop(config):
        shard = train.get_dataset_shard("train")
        train.report({"n": len(shard), "first": shard[0]})

    trainer = JaxTrainer(
        loop,
        datasets={"train": list(range(10))},
        scaling_config=ScalingConfig(num_workers=2, devices_per_worker=4),
        run_config=RunConfig(name="t3", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["n"] == 5


def test_failure_restart_from_checkpoint(rt, tmp_path):
    marker = str(tmp_path / "fail_once")

    def loop(config):
        ctx = train.get_context()
        ckpt = train.get_checkpoint()
        start = ckpt.to_dict()["step"] + 1 if ckpt is not None else 0
        for i in range(start, 4):
            if i == 2 and not os.path.exists(config["marker"]) \
                    and ctx.get_world_rank() == 0:
                open(config["marker"], "w").close()
                os._exit(1)  # hard-kill this worker process
            c = None
            if ctx.get_world_rank() == 0:
                c = Checkpoint.from_dict({"step": i})
            train.report({"step": i}, checkpoint=c)

    trainer = JaxTrainer(
        loop,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=2, devices_per_worker=4),
        run_config=RunConfig(name="t4", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=2)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 3
    # restart resumed from step-1 checkpoint, not from scratch
    steps = [m["step"] for m in result.metrics_history]
    assert steps.count(0) == 1


def test_failure_exhausts_budget(rt, tmp_path):
    def loop(config):
        os._exit(1)

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1, devices_per_worker=4),
        run_config=RunConfig(name="t5", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=0)),
    )
    result = trainer.fit()
    assert result.error is not None
    # the flight record of a worker that is gone is its marker
    from ray_tpu.perf import load_bundle

    ring = load_bundle(result.flight_path)["rings"]["train_worker:0"]
    assert [ev["kind"] for ev in ring] == ["postmortem.fetch_error"]


def _jitting_loop(config):
    import jax
    import jax.numpy as jnp

    def flight_step(x):
        return (x * x).sum()

    step = jax.jit(flight_step)
    for i in range(3):
        train.report({"i": i, "v": float(step(jnp.ones(4) * i))})
    if config.get("boom"):
        raise ValueError("boom at the end of the loop")


@pytest.mark.parametrize("boom", [False, True],
                         ids=["ended_well", "loop_raised"])
def test_fit_leaves_one_flight_record(rt, tmp_path, capsys, boom):
    """ISSUE 38: the workers' rings reach the driver before the workers
    die, on every path out of fit(): one bundle beside the run, in the
    post-mortem's own shape, which its CLI renders."""
    from ray_tpu import cli
    from ray_tpu.perf import load_bundle

    result = JaxTrainer(
        _jitting_loop, train_loop_config={"boom": boom, "pad": 1},
        scaling_config=ScalingConfig(num_workers=1, devices_per_worker=4),
        run_config=RunConfig(name="flight", storage_path=str(tmp_path)),
    ).fit()
    assert (result.error is not None) == boom
    assert result.flight_path == os.path.join(result.path, "flight.json")
    bundle = load_bundle(result.flight_path)
    assert set(bundle["rings"]) == {"driver", "train_worker:0"}
    assert bundle["origin"] == "driver"
    assert bundle["meta"]["iterations"] == 3
    worker = bundle["rings"]["train_worker:0"]
    kinds = [ev["kind"] for ev in worker]
    assert kinds.count("rtpu.train.report") == 3
    assert "rtpu.train.jax_start" in kinds and "rtpu.train.mesh" in kinds
    # the loop's own program, built in the worker after jax came up there
    step = {ev["kind"]: ev for ev in worker
            if ev["kind"].startswith("rtpu.jax.")
            and "flight_step" in ev["label"]}
    assert set(step) == {"rtpu.jax.trace", "rtpu.jax.lower",
                         "rtpu.jax.compile"}
    assert step["rtpu.jax.compile"]["label"] == "jit_flight_step"
    assert step["rtpu.jax.compile"]["data"]["cache"] in ("off", "miss",
                                                         "hit")
    jax_up = next(ev for ev in worker
                  if ev["kind"] == "rtpu.train.jax_start")
    assert step["rtpu.jax.trace"]["ts"] >= jax_up["ts"] + jax_up["dur"]
    # beside the driver's ring, on one clock
    driver = {ev["kind"]: ev for ev in bundle["rings"]["driver"]}
    assert "rtpu.train.setup_mesh" in driver
    assert driver["rtpu.train.setup_mesh"]["ts"] <= jax_up["ts"]
    if boom:
        assert bundle["reason"] == "fit: TrainWorkerError"
        assert "boom at the end of the loop" in bundle["meta"]["error"]
    else:
        assert bundle["reason"] == "fit: ok"
        assert bundle["meta"]["error"] is None
    assert cli.main(["postmortem", result.flight_path, "--tail", "500"]) == 0
    out = capsys.readouterr().out
    assert "train_worker:0" in out and "rtpu.train.report" in out
    assert "rtpu.jax.compile" in out and bundle["reason"] in out


class TestTorchTrainer:
    def test_real_ddp_allreduce_across_gang(self, rt):
        """Smoke: TorchTrainer forms a real gloo process group
        (world_size == 2) and a DDP training loop runs; the identical-
        params allreduce contract is asserted by the next test via
        all_gather."""
        from ray_tpu.train import TorchTrainer, ScalingConfig

        def loop(config):
            import numpy as np
            import torch
            import torch.distributed as dist

            from ray_tpu import train
            from ray_tpu.train import torch as train_torch

            assert dist.is_initialized()
            assert dist.get_world_size() == 2
            rank = train.get_context().get_world_rank()
            torch.manual_seed(0)  # same init on both ranks
            model = torch.nn.Linear(4, 1)
            model = train_torch.prepare_model(model)
            opt = torch.optim.SGD(model.parameters(), lr=0.1)
            # DIFFERENT data per rank: only an allreduce makes the
            # updated params match
            g = torch.Generator().manual_seed(100 + rank)
            x = torch.randn(16, 4, generator=g)
            y = torch.randn(16, 1, generator=g)
            for _ in range(3):
                opt.zero_grad()
                loss = ((model(x) - y) ** 2).mean()
                loss.backward()
                opt.step()
            w = model.module.weight.detach().numpy().copy()
            train.report({"w": w.tolist(), "rank": rank,
                          "world": dist.get_world_size()})

        res = TorchTrainer(
            loop, scaling_config=ScalingConfig(num_workers=2)).fit()
        assert res.error is None
        final = res.metrics_history[-1]
        assert final["world"] == 2
        import numpy as np

        assert np.isfinite(np.asarray(final["w"])).all()

    def test_ddp_params_identical_across_ranks(self, rt):
        """Both ranks report their post-training params; they must be
        bitwise-identical (the allreduce contract)."""
        from ray_tpu.train import TorchTrainer, ScalingConfig

        def loop(config):
            import torch
            import torch.distributed as dist

            from ray_tpu import train
            from ray_tpu.train import torch as train_torch

            rank = train.get_context().get_world_rank()
            torch.manual_seed(rank * 7 + 1)  # DIFFERENT init per rank:
            # DDP's constructor broadcast must erase the difference
            model = train_torch.prepare_model(torch.nn.Linear(3, 2))
            opt = torch.optim.SGD(model.parameters(), lr=0.05)
            g = torch.Generator().manual_seed(rank)
            for _ in range(2):
                x = torch.randn(8, 3, generator=g)
                opt.zero_grad()
                model(x).sum().backward()
                opt.step()
            flat = torch.cat([p.detach().flatten()
                              for p in model.parameters()])
            # allgather both ranks' params and compare IN the workers
            gathered = [torch.zeros_like(flat), torch.zeros_like(flat)]
            dist.all_gather(gathered, flat)
            same = bool(torch.equal(gathered[0], gathered[1]))
            train.report({"same": same})

        res = TorchTrainer(
            loop, scaling_config=ScalingConfig(num_workers=2)).fit()
        assert res.error is None
        assert res.metrics_history[-1]["same"] is True
