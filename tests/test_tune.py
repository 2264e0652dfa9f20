"""Tune: grid/random search, ASHA early stopping, best-result selection."""

import pytest

import ray_tpu
from ray_tpu import tune


def test_grid_search_best(ray_start_regular, tmp_path):
    def objective(config):
        return {"score": -(config["x"] - 3) ** 2}

    results = tune.Tuner(
        objective,
        param_space={"x": tune.grid_search([0, 1, 2, 3, 4, 5])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    max_concurrent_trials=3),
    ).fit()
    assert len(results) == 6
    assert results.get_best_result().config["x"] == 3


def test_random_sampling(ray_start_regular):
    def objective(config):
        return {"val": config["lr"]}

    results = tune.Tuner(
        objective,
        param_space={"lr": tune.loguniform(1e-4, 1e-1)},
        tune_config=tune.TuneConfig(metric="val", mode="min", num_samples=4,
                                    max_concurrent_trials=2),
    ).fit()
    assert len(results) == 4
    for r in results:
        assert 1e-4 <= r.metrics["val"] <= 1e-1


def test_intermediate_reports_and_asha(ray_start_regular):
    def objective(config):
        import time

        for i in range(20):
            tune.report({"loss": 100.0 / config["q"] - i})
            # long enough that the four trials overlap although their
            # workers start one after another on a loaded host
            time.sleep(0.05)
        return {"final": True}

    sched = tune.ASHAScheduler(metric="loss", mode="min", max_t=20,
                               grace_period=2, reduction_factor=2)
    results = tune.Tuner(
        objective,
        param_space={"q": tune.grid_search([1, 2, 4, 8])},
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    scheduler=sched,
                                    max_concurrent_trials=4),
    ).fit()
    assert len(results) == 4
    best = results.get_best_result()
    assert best.config["q"] == 8
    stopped = [r for r in results if r.stopped_early]
    assert stopped, "ASHA should stop at least one losing trial"


def test_trial_error_isolated(ray_start_regular):
    def objective(config):
        if config["x"] == 1:
            raise RuntimeError("bad trial")
        return {"ok": 1}

    results = tune.Tuner(
        objective, param_space={"x": tune.grid_search([0, 1, 2])},
        tune_config=tune.TuneConfig(metric="ok", mode="max"),
    ).fit()
    assert len(results.errors) == 1
    assert results.get_best_result().metrics["ok"] == 1


@pytest.mark.slow
def test_tpe_searcher(ray_start_regular):
    """TPE should concentrate samples near the optimum after startup."""

    def objective(config):
        return {"score": -(config["x"] - 0.7) ** 2}

    results = tune.Tuner(
        objective,
        param_space={"x": tune.uniform(0.0, 1.0)},
        tune_config=tune.TuneConfig(
            metric="score", mode="max", num_samples=24,
            search_alg=tune.TPESearcher(n_startup_trials=6, seed=0),
            max_concurrent_trials=4),
    ).fit()
    assert len(results) == 24
    best = results.get_best_result()
    assert abs(best.config["x"] - 0.7) < 0.2
    # later (post-startup) samples should be closer on average than startup
    xs = [r.config["x"] for r in sorted(results, key=lambda r: r.trial_id)]
    startup = xs[:6]
    late = xs[-8:]
    import statistics
    assert statistics.mean(abs(x - 0.7) for x in late) <= \
        statistics.mean(abs(x - 0.7) for x in startup) + 0.05


def test_concurrency_limiter(ray_start_regular):
    def objective(config):
        return {"v": config["x"]}

    limiter = tune.ConcurrencyLimiter(tune.RandomSearch(seed=1),
                                      max_concurrent=2)
    results = tune.Tuner(
        objective, param_space={"x": tune.uniform(0, 1)},
        tune_config=tune.TuneConfig(metric="v", mode="max", num_samples=5,
                                    search_alg=limiter,
                                    max_concurrent_trials=4),
    ).fit()
    assert len(results) == 5


def test_median_stopping(ray_start_regular):
    def objective(config):
        import time
        for i in range(15):
            tune.report({"acc": config["q"] * (i + 1)})
            time.sleep(0.01)
        return {"done": 1}

    sched = tune.MedianStoppingRule(metric="acc", mode="max",
                                    grace_period=3)
    results = tune.Tuner(
        objective, param_space={"q": tune.grid_search([1, 1, 1, 10])},
        tune_config=tune.TuneConfig(metric="acc", mode="max",
                                    scheduler=sched,
                                    max_concurrent_trials=4),
    ).fit()
    assert len(results) == 4


def test_hyperband_brackets(ray_start_regular):
    def objective(config):
        import time
        for i in range(10):
            tune.report({"loss": 10.0 / config["q"] - i * 0.1})
            time.sleep(0.005)
        return {"fin": 1}

    sched = tune.HyperBandScheduler(metric="loss", mode="min", max_t=9,
                                    reduction_factor=3)
    results = tune.Tuner(
        objective, param_space={"q": tune.grid_search([1, 2, 4, 8])},
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    scheduler=sched,
                                    max_concurrent_trials=4),
    ).fit()
    assert len(results) == 4
    assert results.get_best_result().config["q"] == 8


def test_pbt_exploit_transfers_checkpoint(ray_start_regular):
    """Bottom-quantile trials must clone top checkpoints and perturb lr."""

    def objective(config):
        import time

        start = tune.get_checkpoint()
        score = start["score"] if start else 0.0
        lr = config["lr"]
        for _ in range(30):
            score += lr
            tune.report({"score": score, "lr": lr},
                        checkpoint={"score": score})
            # as above: a trial that ends before the others have
            # reported has nobody to exploit
            time.sleep(0.05)
        return {"score": score}

    sched = tune.PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=5,
        quantile_fraction=0.5,
        hyperparam_mutations={"lr": tune.uniform(0.001, 1.0)}, seed=0)
    results = tune.Tuner(
        objective,
        param_space={"lr": tune.grid_search([0.001, 0.002, 0.5, 1.0])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=sched,
                                    max_concurrent_trials=4),
    ).fit()
    assert len(results) == 4
    # the losers should have been pulled up by exploitation: every trial's
    # final score should be far above what lr=0.001 alone could reach (0.03)
    finals = sorted(r.metrics["score"] for r in results)
    assert finals[0] > 0.1, finals


@pytest.mark.slow
def test_bayesopt_search_beats_random_on_quadratic(ray_start_regular):
    """GP+EI must concentrate samples near the optimum of a smooth
    objective (ref: BayesOptSearch wrapper semantics)."""
    from ray_tpu import tune

    def objective(config):
        return {"score": -(config["x"] - 0.7) ** 2
                         - (config["y"] - 0.3) ** 2}

    searcher = tune.BayesOptSearch(n_startup_trials=5, seed=0)
    results = tune.Tuner(
        objective,
        param_space={"x": tune.uniform(0, 1), "y": tune.uniform(0, 1),
                     "tag": tune.choice(["a", "b"])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    num_samples=20, search_alg=searcher,
                                    max_concurrent_trials=2),
    ).fit()
    best = results.get_best_result()
    assert best.metrics["score"] > -0.02  # within ~0.14 of the optimum
    assert best.config["tag"] in ("a", "b")


def test_bayesopt_loguniform_and_randint(ray_start_regular):
    from ray_tpu import tune

    def objective(config):
        import math

        return {"loss": abs(math.log10(config["lr"]) + 2)
                        + abs(config["layers"] - 3) * 0.1}

    searcher = tune.BayesOptSearch(n_startup_trials=4, seed=1)
    results = tune.Tuner(
        objective,
        param_space={"lr": tune.loguniform(1e-4, 1e-1),
                     "layers": tune.randint(1, 6)},
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    num_samples=16, search_alg=searcher,
                                    max_concurrent_trials=2),
    ).fit()
    best = results.get_best_result()
    assert best.metrics["loss"] < 0.8
    assert isinstance(best.config["layers"], int)
