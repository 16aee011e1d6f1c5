"""Tune engine tests.

Mirrors the reference's tune test strategy (ref: python/ray/tune/tests/
test_tune_controller*.py — controller loop, scheduler decisions, PBT
exploit; test_trainable.py — class/function API)."""
import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.tune import (ASHAScheduler, PopulationBasedTraining, TuneConfig,
                          Tuner)
from ray_tpu.train.config import RunConfig


@pytest.fixture
def rt():
    rt = ray_tpu.init(num_cpus=8)
    yield rt
    ray_tpu.shutdown()


def test_grid_search_function_trainable(rt):
    def train_fn(config):
        for i in range(3):
            tune.report(score=config["x"] * (i + 1))

    results = Tuner(
        train_fn,
        param_space={"x": tune.grid_search([1, 2, 3])},
        tune_config=TuneConfig(metric="score", mode="max"),
    ).fit()
    assert len(results) == 3
    best = results.get_best_result()
    assert best.metrics["score"] == 9  # x=3 at iteration 3
    assert not results.errors


def test_random_search_num_samples(rt):
    def train_fn(config):
        tune.report(score=config["lr"])

    results = Tuner(
        train_fn,
        param_space={"lr": tune.loguniform(1e-4, 1e-1)},
        tune_config=TuneConfig(metric="score", mode="min", num_samples=5),
    ).fit()
    assert len(results) == 5
    lrs = [r.metrics["score"] for r in results]
    assert all(1e-4 <= v <= 1e-1 for v in lrs)
    assert len(set(lrs)) > 1  # actually sampled


def test_class_trainable_and_checkpointing(rt):
    class MyTrainable(tune.Trainable):
        def setup(self, config):
            self.x = config["x"]
            self.count = 0

        def step(self):
            self.count += 1
            return {"score": self.x * self.count}

        def save_checkpoint(self):
            return {"count": self.count}

        def load_checkpoint(self, ck):
            self.count = ck["count"]

    results = Tuner(
        MyTrainable,
        param_space={"x": tune.grid_search([2, 5])},
        tune_config=TuneConfig(metric="score", mode="max"),
        run_config=RunConfig(stop={"training_iteration": 4}),
    ).fit()
    assert len(results) == 2
    assert results.get_best_result().metrics["score"] == 20  # 5 * 4


def test_stop_criteria_metric(rt):
    def train_fn(config):
        for i in range(100):
            tune.report(loss=100 - i)

    results = tune.run(train_fn, config={}, metric="loss", mode="min",
                       stop={"training_iteration": 5})
    assert len(results) == 1
    assert results[0].metrics["training_iteration"] == 5


def test_asha_stops_bad_trials_early(rt):
    """ASHA judges a trial against those that reached the rung BEFORE it,
    and the first to reach a rung always goes on. A trial here reports
    twenty times with nothing between two reports, so on a loaded machine
    the trials reach every rung one after the other, in the order they
    were started. Started best first, each later trial finds the rung's
    cutoff above it, whatever the machine does; started worst first (as
    this test did), each was the best so far and NONE was stopped."""
    def train_fn(config):
        for i in range(20):
            tune.report(score=config["q"] * (i + 1), q=config["q"])

    asha = ASHAScheduler(grace_period=2, reduction_factor=2, max_t=20)
    results = Tuner(
        train_fn,
        param_space={"q": tune.grid_search([8, 7, 6, 5, 4, 3, 2, 1])},
        tune_config=TuneConfig(
            metric="score", mode="max", max_concurrent_trials=8,
            scheduler=asha),
    ).fit()
    assert len(results) == 8
    iters = {r.metrics["q"]: r.metrics["training_iteration"]
             for r in results}
    # every trial was judged at the first rung; bad trials got cut before
    # max_t; the best is in the top half of every rung it reaches
    assert len(asha._rungs[2]) == 8
    assert min(iters.values()) < 20
    assert iters[8] == 20


def test_pbt_exploit_and_explore(rt):
    """>=8 trials; verify bottom trials adopted (perturbed) top configs:
    the reported lr must change mid-history for at least one trial."""

    def train_fn(config):
        ck = tune.get_checkpoint() or {}
        step = int(ck.get("step", 0))
        for _ in range(12 - step):
            step += 1
            tune.report({"score": config["lr"] * step, "lr": config["lr"]},
                        checkpoint={"step": step})

    pbt = PopulationBasedTraining(
        perturbation_interval=2,
        hyperparam_mutations={"lr": tune.uniform(0.1, 10.0)},
        seed=7)
    results = Tuner(
        train_fn,
        param_space={"lr": tune.uniform(0.1, 10.0)},
        tune_config=TuneConfig(metric="score", mode="max", num_samples=8,
                               max_concurrent_trials=8, scheduler=pbt,
                               seed=3),
        run_config=RunConfig(stop={"training_iteration": 12}),
    ).fit()
    assert len(results) == 8
    assert not results.errors
    perturbed = 0
    for r in results:
        lrs = {round(m["lr"], 6) for m in (r.metrics_history or []) if "lr" in m}
        if len(lrs) > 1:
            perturbed += 1
    assert perturbed >= 1, "PBT never exploited/explored any trial"


def test_trainer_under_tune(rt):
    """Train runs through Tune (ref: base_trainer.py:829 pattern)."""
    from ray_tpu import train
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    def loop(config):
        for i in range(2):
            train.report({"loss": config.get("lr", 1.0) * (i + 1)})

    trainer = DataParallelTrainer(
        loop, train_loop_config={"lr": 1.0},
        scaling_config=ScalingConfig(num_workers=1))
    results = Tuner(
        trainer,
        param_space={"lr": tune.grid_search([0.5, 2.0])},
        tune_config=TuneConfig(metric="loss", mode="min"),
    ).fit()
    assert len(results) == 2
    assert not results.errors
    # last reported entry per trial: lr * 2
    best = results.get_best_result()
    assert best.metrics["loss"] == pytest.approx(1.0)


def test_hyperband_brackets_promote_and_stop(rt):
    """Synchronous HyperBand: trials pause at rung boundaries, rungs
    promote the top 1/eta when full, losers stop early."""
    from ray_tpu.tune import HyperBandScheduler

    iters_run = {}

    def train_fn(config):
        ck = tune.get_checkpoint()
        start = (ck or {}).get("it", 0)
        for i in range(start, 100):
            tune.report(score=config["q"] * (i + 1),
                        training_iteration=i + 1,
                        checkpoint={"it": i + 1})

    results = Tuner(
        train_fn,
        param_space={"q": tune.grid_search([1, 2, 3, 4, 5, 6])},
        tune_config=TuneConfig(
            metric="score", mode="max", max_concurrent_trials=3,
            scheduler=HyperBandScheduler(max_t=9, reduction_factor=3)),
    ).fit()
    assert len(results) == 6
    assert not results.errors
    iters = sorted(len(r.metrics_history) for r in results)
    # early-stopped losers ran fewer iterations than max_t survivors
    assert iters[0] < 9
    assert iters[-1] <= 9
    best = results.get_best_result()
    assert best.metrics["config"]["q"] == 6  # highest slope survives


def test_tpe_searcher_beats_random_on_quadratic(rt):
    """TPE concentrates samples near the optimum of a smooth objective."""
    from ray_tpu.tune import TPESearcher

    def objective(config):
        x = config["x"]
        tune.report(loss=(x - 3.0) ** 2)

    searcher = TPESearcher(n_initial_points=6, seed=0)
    results = Tuner(
        objective,
        param_space={"x": tune.uniform(-10.0, 10.0)},
        tune_config=TuneConfig(metric="loss", mode="min", num_samples=30,
                               max_concurrent_trials=4,
                               search_alg=searcher),
    ).fit()
    assert len(results) == 30
    best = results.get_best_result()
    assert abs(best.metrics["config"]["x"] - 3.0) < 1.5
    # the second half of suggestions should cluster nearer the optimum
    xs = [r.metrics["config"]["x"] for r in results]
    early = sum(abs(x - 3.0) for x in xs[:10]) / 10
    late = sum(abs(x - 3.0) for x in xs[-10:]) / 10
    assert late < early


def test_tpe_with_choice_and_loguniform(rt):
    from ray_tpu.tune import TPESearcher

    def objective(config):
        bonus = 1.0 if config["act"] == "gelu" else 0.0
        tune.report(score=bonus - abs(config["lr"] - 1e-3) / 1e-3)

    results = Tuner(
        objective,
        param_space={"lr": tune.loguniform(1e-5, 1e-1),
                     "act": tune.choice(["relu", "gelu", "tanh"])},
        tune_config=TuneConfig(metric="score", mode="max", num_samples=25,
                               search_alg=TPESearcher(n_initial_points=8,
                                                      seed=1)),
    ).fit()
    assert len(results) == 25
    assert not results.errors


def test_experiment_snapshot_and_restore(rt, tmp_path):
    """fit() writes experiment_state.pkl; Tuner.restore resumes finished
    trials without re-running them and completes pending work."""
    calls = []

    def train_fn(config):
        for i in range(3):
            tune.report(score=config["x"] * (i + 1))

    rc = RunConfig(name="exp1", storage_path=str(tmp_path))
    results = Tuner(
        train_fn,
        param_space={"x": tune.grid_search([1, 2, 3])},
        tune_config=TuneConfig(metric="score", mode="max"),
        run_config=rc).fit()
    assert len(results) == 3
    state_file = tmp_path / "exp1" / "experiment_state.pkl"
    assert state_file.exists()

    # restore the finished experiment: results preserved, nothing re-runs
    restored = Tuner.restore(str(tmp_path / "exp1"), train_fn).fit()
    assert len(restored) == 3
    assert restored.get_best_result().metrics["score"] == 9


def test_restore_resumes_inflight_trial_from_checkpoint(rt, tmp_path):
    """A snapshot taken mid-run marks running trials PENDING with their
    checkpoint; restore must continue from the checkpoint, not iter 0."""
    import cloudpickle

    from ray_tpu.tune.tuner import TuneController

    def train_fn(config):
        ck = tune.get_checkpoint()
        start = (ck or {}).get("it", 0)
        for i in range(start, 4):
            tune.report(score=i + 1, it_seen=start,
                        checkpoint={"it": i + 1})

    rc = RunConfig(name="exp2", storage_path=str(tmp_path))
    ctrl = TuneController(train_fn, {"x": tune.grid_search([1])},
                          TuneConfig(metric="score", mode="max"), rc)
    # hand-build the interrupted state: one trial mid-flight at iter 2
    state = ctrl.snapshot_state()
    state["trials"] = [{
        "trial_id": "trial_mid", "config": {"x": 1}, "status": "PENDING",
        "last_result": {"score": 2}, "metrics_history": [{"score": 1},
                                                         {"score": 2}],
        "latest_checkpoint": {"it": 2},
    }]
    state["exhausted"] = True
    exp_dir = tmp_path / "exp2"
    exp_dir.mkdir(parents=True)
    with open(exp_dir / "experiment_state.pkl", "wb") as f:
        cloudpickle.dump(state, f)

    results = Tuner.restore(str(exp_dir), train_fn).fit()
    assert len(results) == 1
    r = results[0]
    assert r.error is None
    # resumed from it=2: first report carries it_seen=2, final score 4
    assert r.metrics["score"] == 4
    assert r.metrics["it_seen"] == 2


def test_pb2_gp_explore_and_exploit(rt):
    """PB2: same exploit machinery as PBT, GP-UCB explore within bounds —
    configs must change mid-history AND stay inside the bounds."""
    from ray_tpu.tune import PB2

    def train_fn(config):
        ck = tune.get_checkpoint() or {}
        step = int(ck.get("step", 0))
        for _ in range(12 - step):
            step += 1
            tune.report({"score": config["lr"] * step, "lr": config["lr"]},
                        checkpoint={"step": step})

    pb2 = PB2(perturbation_interval=2,
              hyperparam_bounds={"lr": (0.1, 10.0)}, seed=7)
    results = Tuner(
        train_fn,
        param_space={"lr": tune.uniform(0.1, 10.0)},
        tune_config=TuneConfig(metric="score", mode="max", num_samples=8,
                               max_concurrent_trials=8, scheduler=pb2,
                               seed=3),
        run_config=RunConfig(stop={"training_iteration": 12}),
    ).fit()
    assert len(results) == 8
    assert not results.errors
    perturbed = 0
    for r in results:
        lrs = {round(m["lr"], 6) for m in (r.metrics_history or [])
               if "lr" in m}
        if len(lrs) > 1:
            perturbed += 1
        assert all(0.1 <= lr <= 10.0 for lr in lrs), lrs
    assert perturbed >= 1, "PB2 never exploited/explored any trial"


def test_pb2_gp_prefers_better_region():
    """Unit-level: after observations showing high-x improves more, the
    GP-UCB explore proposes configs in the better half."""
    from ray_tpu.tune.pb2 import PB2

    pb2 = PB2(hyperparam_bounds={"x": (0.0, 1.0)}, log_scale=False, seed=0)
    # improvement grows with x
    for i in range(20):
        x = i / 19.0
        pb2._obs_X.append([1.0, x])
        pb2._obs_y.append(x * 2.0 + 0.01 * (i % 3))
    picks = [pb2._explore({"x": 0.5})["x"] for _ in range(5)]
    assert sum(p > 0.6 for p in picks) >= 4, picks


def test_tune_syncer_roundtrip_and_restore(rt, tmp_path):
    """Experiment syncs to an fsspec remote (memory://) during the run;
    pulling it onto a fresh path restores the sweep with all results
    (ref: tune/syncer.py:345 + Tuner.restore)."""
    from ray_tpu.tune import Tuner, pull_experiment

    def train_fn(config):
        for i in range(3):
            tune.report({"score": config["a"] * (i + 1)},
                        checkpoint={"i": i})

    remote = "memory://synced_exp"
    results = Tuner(
        train_fn,
        param_space={"a": tune.grid_search([1.0, 2.0])},
        tune_config=TuneConfig(metric="score", mode="max"),
        run_config=RunConfig(name="sync_exp",
                             storage_path=str(tmp_path / "local"),
                             upload_dir=remote, sync_period_s=0.0),
    ).fit()
    assert len(results) == 2 and not results.errors

    # the remote mirror has the experiment state
    import fsspec

    fs = fsspec.filesystem("memory")
    assert any(p.endswith("experiment_state.pkl")
               for p in fs.find("/synced_exp"))

    # restore on a "fresh machine": pull the mirror, Tuner.restore
    fresh = str(tmp_path / "pulled")
    local_exp = pull_experiment(remote, fresh)
    restored = Tuner.restore(local_exp, train_fn).fit()
    assert len(restored) == 2 and not restored.errors
    assert restored.get_best_result().metrics["score"] == 6.0


@pytest.mark.slow  # 61 s alone, 95 s beside five workers (48 trial actors)
@pytest.mark.time_limit(300)
def test_gp_searcher_beats_random_on_quadratic(rt):
    """The native GP-EI searcher (pb2's GP promoted) concentrates near
    the optimum of a smooth deterministic surface."""
    from ray_tpu.tune import GPSearcher, RandomSearch

    def objective(config):
        x, y = config["x"], config["y"]
        tune.report(loss=(x - 2.0) ** 2 + (y + 1.0) ** 2)

    def run_with(searcher):
        res = Tuner(
            objective,
            param_space={"x": tune.uniform(-10.0, 10.0),
                         "y": tune.uniform(-10.0, 10.0)},
            tune_config=TuneConfig(metric="loss", mode="min",
                                   num_samples=24,
                                   max_concurrent_trials=2,
                                   search_alg=searcher),
        ).fit()
        return res.get_best_result().metrics["loss"]

    gp_best = run_with(GPSearcher(n_initial_points=6, seed=1))
    rnd_best = run_with(RandomSearch(num_samples=24, seed=1))
    assert gp_best < 1.5, gp_best
    assert gp_best <= rnd_best * 1.5  # at worst comparable, usually better


def test_bohb_beats_random_at_equal_budget(rt):
    """The VERDICT bar: BOHB (model-based searcher + HyperBand brackets)
    finds a better config than random search given the SAME total
    training-iteration budget on a deterministic surface."""
    from ray_tpu.tune import (GPSearcher, HyperBandForBOHB, RandomSearch)

    class Surface(tune.Trainable):
        def setup(self, config):
            self.x = config["x"]
            self.t = 0

        def step(self):
            self.t += 1
            # converges toward the config's true quality with iteration
            quality = -(self.x - 0.7) ** 2
            return {"score": quality * (1 - 0.5 ** self.t),
                    "training_iteration": self.t}

        def save_checkpoint(self):
            return {"t": self.t, "x": self.x}

        def load_checkpoint(self, ckpt):
            self.t, self.x = ckpt["t"], ckpt["x"]

    space = {"x": tune.uniform(0.0, 1.0)}

    def total_iters(results):
        return sum(r.metrics.get("training_iteration", 0) for r in results)

    bohb = Tuner(
        Surface, param_space=space,
        tune_config=TuneConfig(
            metric="score", mode="max", num_samples=16,
            max_concurrent_trials=4,
            search_alg=GPSearcher(n_initial_points=4, seed=2),
            scheduler=HyperBandForBOHB(time_attr="training_iteration",
                                       max_t=9, reduction_factor=3)),
    ).fit()
    bohb_best = bohb.get_best_result().metrics["score"]
    bohb_budget = total_iters(bohb)

    # random search with the SAME iteration budget: every trial runs to
    # max_t, so it affords fewer configs
    n_rand = max(2, bohb_budget // 9)
    rnd = Tuner(
        Surface, param_space=space,
        tune_config=TuneConfig(
            metric="score", mode="max", num_samples=int(n_rand),
            max_concurrent_trials=4,
            search_alg=RandomSearch(num_samples=int(n_rand), seed=2)),
        run_config=tune.RunConfig(stop={"training_iteration": 9}),
    ).fit()
    rnd_best = rnd.get_best_result().metrics["score"]
    assert bohb_best >= rnd_best - 1e-6, (bohb_best, rnd_best)


def test_resource_changing_scheduler(rt):
    """A trial's resources change mid-run: the scheduler pauses
    (checkpoint), reallocates, and resumes — the trainable only sees a
    normal save/restore."""
    from ray_tpu.tune import ResourceChangingScheduler

    class T(tune.Trainable):
        def setup(self, config):
            self.t = 0

        def step(self):
            self.t += 1
            return {"score": float(self.t), "training_iteration": self.t}

        def save_checkpoint(self):
            return {"t": self.t}

        def load_checkpoint(self, ckpt):
            self.t = ckpt["t"]

    def alloc(controller, trial, result, scheduler):
        # bump to 2 CPUs once the trial passes iteration 2
        if result.get("training_iteration", 0) >= 2:
            return {"CPU": 2.0}
        return None

    results = Tuner(
        T, param_space={},
        tune_config=TuneConfig(
            metric="score", mode="max", num_samples=1,
            scheduler=ResourceChangingScheduler(
                resources_allocation_function=alloc)),
        run_config=tune.RunConfig(stop={"training_iteration": 6}),
    ).fit()
    r = results.get_best_result()
    assert r.metrics["training_iteration"] >= 6
    # the override stuck on the trial
    trial = results._trials[0] if hasattr(results, "_trials") else None
    if trial is not None:
        assert trial.resources == {"CPU": 2.0}


def test_concurrency_limiter_caps_inflight(rt):
    """The limiter must keep the wrapped searcher's in-flight count at
    max_concurrent without ending the experiment (PENDING, not None)."""
    from ray_tpu.tune import ConcurrencyLimiter, TPESearcher

    seen_live = []

    class Spy(TPESearcher):
        def suggest(self, tid):
            return super().suggest(tid)

    limiter = ConcurrencyLimiter(Spy(seed=0), max_concurrent=2)
    orig_suggest = limiter.suggest

    def counting_suggest(tid):
        seen_live.append(len(limiter._live))
        return orig_suggest(tid)

    limiter.suggest = counting_suggest

    def train_fn(config):
        tune.report(score=config["x"])

    results = Tuner(
        train_fn,
        param_space={"x": tune.uniform(0, 1)},
        tune_config=TuneConfig(metric="score", mode="max", num_samples=6,
                               search_alg=limiter),
    ).fit()
    assert len(results) == 6
    assert not results.errors
    assert max(seen_live) <= 2  # never more than 2 outstanding


def test_repeater_averages_noisy_objective(rt):
    """Each config runs `repeat` times; the inner searcher sees ONE
    averaged observation per config."""
    from ray_tpu.tune import Repeater, TPESearcher

    inner = TPESearcher(seed=1)
    completed = []
    orig = inner.on_trial_complete

    def spy_complete(tid, result):
        completed.append(result)
        return orig(tid, result)

    inner.on_trial_complete = spy_complete
    rep = Repeater(inner, repeat=3)

    def train_fn(config):
        import random as _r

        tune.report(score=config["x"] + _r.Random().uniform(-0.1, 0.1))

    results = Tuner(
        train_fn,
        param_space={"x": tune.uniform(0, 1)},
        tune_config=TuneConfig(metric="score", mode="max", num_samples=6,
                               search_alg=rep),
    ).fit()
    assert len(results) == 6            # 2 configs x 3 repeats
    assert not results.errors
    assert len(completed) == 2          # inner saw one mean per config
    xs = sorted(set(round(r.metrics["config"]["x"], 6) for r in results))
    assert len(xs) == 2                 # exactly two distinct configs


def test_repeater_flushes_truncated_group(rt):
    """num_samples that isn't a multiple of `repeat` truncates the last
    group; the experiment-end hook must still report its partial mean to
    the inner searcher (no leaked pending state)."""
    from ray_tpu.tune import Repeater, TPESearcher

    inner = TPESearcher(seed=2)
    completed = []
    orig = inner.on_trial_complete

    def spy_complete(tid, result):
        completed.append((tid, result))
        return orig(tid, result)

    inner.on_trial_complete = spy_complete
    rep = Repeater(inner, repeat=3)

    def train_fn(config):
        tune.report(score=config["x"])

    results = Tuner(
        train_fn,
        param_space={"x": tune.uniform(0, 1)},
        tune_config=TuneConfig(metric="score", mode="max", num_samples=4,
                               search_alg=rep),
    ).fit()
    assert len(results) == 4            # 1 full group + 1 single-run
    assert not results.errors
    assert len(completed) == 2          # truncated group flushed too
    assert not rep._groups              # nothing leaked
    assert not inner._suggested         # inner pending state resolved
