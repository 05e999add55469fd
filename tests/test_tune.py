"""Tuner tests. Parity: ``python/ray/tune/tests`` patterns (SURVEY.md §4)."""

import pytest

import ray_tpu
from ray_tpu import train, tune
from ray_tpu.train import RunConfig
from ray_tpu.tune import ASHAScheduler, MedianStoppingRule, TuneConfig, Tuner


def test_grid_search(ray_start_regular, tmp_path):
    def objective(config):
        train.report({"score": config["a"] * 10 + config["b"]})

    tuner = Tuner(
        objective,
        param_space={"a": tune.grid_search([1, 2]), "b": tune.grid_search([3, 4])},
        tune_config=TuneConfig(metric="score", mode="max"),
        run_config=RunConfig(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert len(grid) == 4
    best = grid.get_best_result(metric="score", mode="max")
    assert best.metrics["score"] == 24
    assert best.metrics["config"] == {"a": 2, "b": 4}


def test_random_sampling(ray_start_regular, tmp_path):
    def objective(config):
        train.report({"val": config["x"]})

    tuner = Tuner(
        objective,
        param_space={"x": tune.uniform(0, 1)},
        tune_config=TuneConfig(num_samples=3, seed=42),
        run_config=RunConfig(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert len(grid) == 3
    vals = [r.metrics["val"] for r in grid]
    assert all(0 <= v <= 1 for v in vals)
    assert len(set(vals)) == 3  # distinct samples


def test_trial_error_isolated(ray_start_regular, tmp_path):
    def objective(config):
        if config["i"] == 1:
            raise RuntimeError("trial exploded")
        train.report({"ok": 1})

    tuner = Tuner(
        objective,
        param_space={"i": tune.grid_search([0, 1, 2])},
        run_config=RunConfig(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert len(grid.errors) == 1
    ok = [r for r in grid if r.error is None]
    assert len(ok) == 2


def test_asha_stops_bad_trials(ray_start_regular, tmp_path):
    import time

    def objective(config):
        for i in range(1, 21):
            # bad trials have high loss and would run long if not stopped; the worse a trial, the later it reports, so
            # that the fourth to reach a rung (the one the halving judges) is not the best one, whatever the start-up order
            train.report({"loss": config["q"] + i * 0.0})
            time.sleep(0.05 * config["q"])

    tuner = Tuner(
        objective,
        param_space={"q": tune.grid_search([1.0, 2.0, 3.0, 4.0])},
        tune_config=TuneConfig(
            metric="loss",
            mode="min",
            scheduler=ASHAScheduler(
                metric="loss", mode="min", grace_period=2, reduction_factor=4, max_t=20
            ),
            max_concurrent_trials=4,
        ),
        run_config=RunConfig(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    best = grid.get_best_result(metric="loss", mode="min")
    assert best.metrics["loss"] == 1.0
    # at least one of the worse trials was cut before max_t
    iters = [r.metrics["training_iteration"] for r in grid]
    assert min(iters) < 20


def test_tuner_wraps_jax_trainer(ray_start_regular, tmp_path):
    from ray_tpu.train import JaxTrainer, ScalingConfig

    def loop(config):
        train.report({"loss": 100.0 - config["lr"]})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path), name="inner"),
    )
    tuner = Tuner(
        trainer,
        param_space={"lr": tune.grid_search([1.0, 2.0])},
        tune_config=TuneConfig(metric="loss", mode="min", max_concurrent_trials=1),
        run_config=RunConfig(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    best = grid.get_best_result(metric="loss", mode="min")
    assert best.metrics["loss"] == 98.0


def test_median_stopping_rule():
    rule = MedianStoppingRule(metric="loss", mode="min", grace_period=0, min_samples_required=2)
    assert rule.on_result("a", 1, {"loss": 1.0}) == "CONTINUE"
    assert rule.on_result("b", 1, {"loss": 1.2}) == "CONTINUE"
    assert rule.on_result("c", 1, {"loss": 50.0}) == "STOP"


def test_pbt_exploits_better_config(ray_start_regular, tmp_path):
    from ray_tpu import tune
    from ray_tpu.train import RunConfig
    from ray_tpu.tune import PopulationBasedTraining, TuneConfig

    def trainable(config):
        from ray_tpu.train import report

        import time as _t

        # score is simply the lr: PBT must migrate lr=0 trials to lr=1
        # (slow iterations so the controller can interject exploits)
        for _ in range(14):
            report({"score": config["lr"]})
            _t.sleep(0.25)

    tuner = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search([0.0, 0.0, 1.0])},
        tune_config=TuneConfig(
            num_samples=1,
            scheduler=PopulationBasedTraining(
                metric="score",
                mode="max",
                perturbation_interval=2,
                hyperparam_mutations={"lr": [0.0, 1.0]},
                quantile_fraction=0.4,
                seed=0,
            ),
        ),
        run_config=RunConfig(storage_path=str(tmp_path), name="pbt"),
    )
    results = tuner.fit()
    finals = [r.metrics["score"] for r in results]
    # every surviving trial converges onto the winning config
    assert max(finals) == 1.0
    assert sum(1 for s in finals if s == 1.0) >= 2, finals


def test_tuner_restore_resumes_experiment(ray_start_regular, tmp_path):
    import os
    import signal
    import subprocess
    import sys
    import textwrap
    import time as _time

    import ray_tpu as rt
    from ray_tpu import tune

    exp_dir = str(tmp_path / "exp")
    script = textwrap.dedent(f"""
        import ray_tpu, time
        from ray_tpu import tune
        from ray_tpu.train import RunConfig, report
        ray_tpu.init(num_cpus=2)

        def slow_trial(config):
            for i in range(40):
                report({{"step": i, "tag": config["tag"]}})
                time.sleep(0.5)

        tune.Tuner(
            slow_trial,
            param_space={{"tag": tune.grid_search([1, 2])}},
            tune_config=tune.TuneConfig(num_samples=1, max_concurrent_trials=2),
            run_config=RunConfig(storage_path={str(tmp_path)!r}, name="exp"),
        ).fit()
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(rt.__file__)))
    proc = subprocess.Popen([sys.executable, "-c", script], env=env)
    # wait for the snapshot to appear, then kill the driver mid-sweep
    deadline = _time.monotonic() + 60
    state_file = os.path.join(exp_dir, "experiment_state.pkl")
    while _time.monotonic() < deadline:
        if os.path.exists(state_file):
            break
        _time.sleep(0.2)
    else:
        proc.kill()
        raise TimeoutError("snapshot never appeared")
    _time.sleep(1.0)
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=15)

    def fast_trial(config):
        from ray_tpu.train import report

        for i in range(3):
            report({"step": i, "tag": config["tag"]})

    tuner = tune.Tuner.restore(exp_dir, trainable=fast_trial)
    results = tuner.fit()
    tags = sorted(r.metrics["tag"] for r in results)
    assert tags == [1, 2]  # both trials resumed and completed
    assert all(r.error is None for r in results)


def test_stoppers_and_loggers(ray_start_regular, tmp_path):
    import json
    import os

    def objective(config):
        for i in range(50):
            train.report({"score": i})

    tuner = Tuner(
        objective,
        param_space={"a": tune.grid_search([1, 2])},
        tune_config=TuneConfig(metric="score", mode="max"),
        run_config=RunConfig(
            storage_path=str(tmp_path), name="stoptest",
            stop=tune.MaximumIterationStopper(5),
        ),
    )
    grid = tuner.fit()
    assert all(r.metrics["training_iteration"] <= 6 for r in grid)
    # result.json + progress.csv written into each trial dir
    trial_dirs = [r.path for r in grid]
    for d in trial_dirs:
        lines = open(os.path.join(d, "result.json")).read().splitlines()
        assert 1 <= len(lines) <= 6
        assert "score" in json.loads(lines[0])
        csv_text = open(os.path.join(d, "progress.csv")).read()
        assert csv_text.startswith("score")


def test_plateau_stopper(ray_start_regular, tmp_path):
    def objective(config):
        for i in range(40):
            train.report({"loss": 1.0 if i > 5 else 10.0 - i})

    grid = Tuner(
        objective,
        tune_config=TuneConfig(metric="loss", mode="min"),
        run_config=RunConfig(
            storage_path=str(tmp_path),
            stop=tune.TrialPlateauStopper("loss", std=1e-6, num_results=4),
        ),
    ).fit()
    assert grid[0].metrics["training_iteration"] < 40


def test_dict_stop_criteria(ray_start_regular, tmp_path):
    def objective(config):
        for i in range(100):
            train.report({"score": i})

    grid = Tuner(
        objective,
        run_config=RunConfig(storage_path=str(tmp_path), stop={"score": 7}),
    ).fit()
    assert grid[0].metrics["score"] <= 8


def test_bayesopt_beats_random_on_quadratic(ray_start_regular, tmp_path):
    """GP search should concentrate samples near the optimum of a smooth
    1-d objective and find a better best-value than coarse random search."""
    from ray_tpu.tune import BayesOptSearch, bayesopt

    def objective(config):
        x = config["x"]
        train.report({"neg_loss": -((x - 0.73) ** 2)})

    tuner = Tuner(
        objective,
        param_space={"x": bayesopt.uniform(0.0, 1.0)},
        tune_config=TuneConfig(
            num_samples=16,
            max_concurrent_trials=1,  # sequential: each suggest sees history
            search_alg=BayesOptSearch(metric="neg_loss", mode="max", seed=0,
                                      n_initial_points=4),
        ),
        run_config=RunConfig(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    best = grid.get_best_result(metric="neg_loss", mode="max")
    assert best.metrics["neg_loss"] > -0.01  # within 0.1 of the optimum
    assert abs(best.metrics["config"]["x"] - 0.73) < 0.1


def test_hyperband_brackets_stop_bad_trials():
    """HyperBand: bracketed halving stops weak trials at rungs (before
    exhausting max_t) while the best survive, with bracket diversity in
    grace periods."""
    from ray_tpu.tune.schedulers import STOP, HyperBandScheduler

    sched = HyperBandScheduler(metric="score", mode="max", max_t=27, reduction_factor=3)
    graces = sorted({b.grace for b in sched._brackets})
    assert len(graces) > 1, "expected multiple bracket budgets"

    # 12 trials; good trials report first so rungs are populated when the
    # weak ones arrive (async halving judges against filled rungs)
    order = sorted(range(12), reverse=True)
    stopped_at = {}
    for it in range(1, 28):
        for i in order:
            tid = f"t{i}"
            if tid in stopped_at:
                continue
            if sched.on_result(tid, it, {"score": float(i)}) == STOP:
                stopped_at[tid] = it
    assert stopped_at.get("t11", 27) >= 27, "best trial must reach max_t"
    early = {t for t, it in stopped_at.items() if it < 27}
    assert len(early) >= 3, f"halving never stopped weak trials early: {stopped_at}"
    assert all(int(t[1:]) < 11 for t in early)


def test_with_parameters_shares_payload(ray_start_regular, tmp_path):
    """tune.with_parameters: one object-store copy feeds every trial."""
    import numpy as np

    from ray_tpu import tune

    payload = np.arange(20000.0)  # too big to want per-trial pickling

    def train_fn(config, data=None):
        from ray_tpu import train as _train

        _train.report({"loss": float(config["x"] + data.sum() * 0)})

    from ray_tpu.train import RunConfig as _RC

    tuner = tune.Tuner(
        tune.with_parameters(train_fn, data=payload),
        param_space={"x": tune.grid_search([1.0, 2.0, 3.0])},
        run_config=_RC(storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert sorted(r.metrics["loss"] for r in grid) == [1.0, 2.0, 3.0]
