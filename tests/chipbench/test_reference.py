"""(e) The plain references against ``models/nn.py`` at tiny sizes, and
the arithmetic of ``flops.py``."""

import types

import jax
import numpy as np
import pytest

import flops
from harness import correct, manifest
from harness.stats import median, percentile

from gordo_tpu.models import nn
from gordo_tpu.models.factories.feedforward_autoencoder import feedforward_hourglass
from gordo_tpu.models.factories.lstm_autoencoder import lstm_symmetric


def _estimator(spec, seed=0):
    init = nn.init_fn_for(spec)
    params = jax.tree_util.tree_map(np.asarray, init(jax.random.PRNGKey(seed), spec))
    return types.SimpleNamespace(spec_=spec, params_=params)


def _program_forward(estimator, x):
    forward = nn.forward_fn_for(estimator.spec_)
    return np.asarray(forward(estimator.spec_, estimator.params_, x)[0])


CASES = {
    "dense_autoencoder": lambda: feedforward_hourglass(20),
    "lstm_autoencoder": lambda: lstm_symmetric(
        5, lookback_window=6, dims=(8, 4), funcs=("tanh", "tanh")
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reference_forward_agrees_with_the_program(name):
    reference = manifest.load_module(manifest.ROOT, "reference", name)
    estimator = _estimator(CASES[name]())
    rows = np.random.RandomState(1).rand(40, estimator.spec_.n_features).astype(np.float32)
    x = reference.model_input(estimator, rows)
    got = reference.forward(reference.layers_of(estimator), x)
    checks = correct.Checks()
    checks.compare(name, _program_forward(estimator, x), got, "cpu")
    assert checks.ok, checks.failures
    assert got.shape[0] == (40 if name == "dense_autoencoder" else 35)


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_wrong_weight_fails_the_tolerance_of_either_platform(name):
    reference = manifest.load_module(manifest.ROOT, "reference", name)
    estimator = _estimator(CASES[name]())
    rows = np.random.RandomState(1).rand(40, estimator.spec_.n_features).astype(np.float32)
    x = reference.model_input(estimator, rows)
    expected = reference.forward(reference.layers_of(estimator), x)
    wrong = _estimator(CASES[name](), seed=1)  # another model entirely
    for platform in ("cpu", "tpu"):
        checks = correct.Checks()
        checks.compare(name, _program_forward(wrong, x), expected, platform)
        assert not checks.ok
    # one perturbed weight of the output layer, on the CPU's tolerance
    estimator.params_["out"]["W"] = estimator.params_["out"]["W"].copy()
    estimator.params_["out"]["W"][0, 0] += 0.5
    checks = correct.Checks()
    checks.compare(name, _program_forward(estimator, x), expected, "cpu")
    assert not checks.ok


def test_compare_refuses_shapes_and_non_finite_values():
    checks = correct.Checks()
    checks.compare("shape", np.zeros((2, 3)), np.zeros((3, 2)), "cpu")
    checks.compare("nan", np.array([np.nan]), np.array([0.0]), "cpu")
    assert len(checks.failures) == 2
    assert correct.TOLERANCE == {"tpu": 5e-2, "cpu": 1e-4}


def test_reference_fit_trains_and_its_band_holds_its_own_fits():
    reference = manifest.load_module(manifest.ROOT, "reference", "dense_autoencoder")
    config = manifest.Cell(manifest.load_manifest(), "hourglass_build").config
    rng = np.random.RandomState(0)
    X = rng.rand(256, 20).astype(np.float32)
    y = (X * 3.0 + 1.0).astype(np.float32)
    args = (X, y, config["layer_dims"], ["tanh"] * 6 + ["linear"])
    one = reference.fit_final_loss(*args, epochs=1, batch_size=32, learning_rate=1e-3, seed=0)
    five = reference.fit_final_loss(*args, epochs=5, batch_size=32, learning_rate=1e-3, seed=0)
    assert five < one  # it learns
    low, high = reference.loss_band(X, y, config)
    assert low < five < high and not (low < one * 4 < high) or one * 4 > high


def test_reference_gradients_match_finite_differences():
    reference = manifest.load_module(manifest.ROOT, "reference", "dense_autoencoder")
    rng = np.random.RandomState(0)
    layers = reference._init([4, 3, 4], ["tanh", "linear"], rng)
    layers = [(W.astype(np.float64), b.astype(np.float64), a) for W, b, a in layers]
    X, y = rng.rand(8, 4), rng.rand(8, 4)
    loss, grads = reference._gradients(layers, X, y)
    W, b, a = layers[0]
    bumped = W.copy()
    bumped[1, 2] += 1e-6
    loss2, _ = reference._gradients([(bumped, b, a), layers[1]], X, y)
    assert abs((loss2 - loss) / 1e-6 - grads[0][0][1, 2]) < 1e-5


def test_flops_from_shapes():
    # 2 x (20*17 + 17*13 + 13*10 + 10*10 + 10*13 + 13*17 + 17*20): the
    # program's own span stamps 2964 a sample on the same model
    assert flops.dense_forward_flops(20, [17, 13, 10, 10, 13, 17]) == 2964
    lstm = flops.lstm_forward_flops(50, [256, 128, 64, 64, 128, 256], 60)
    assert 12.0e9 < 3 * 32 * lstm < 13.0e9  # ISSUE 23: 12.6 GFLOP a member-step
    assert flops.fit_steps(16384, 32, 5) == 2560
    assert flops.fold_train_rows(12961, 3) == [3241, 6481, 9721]
    config = {"tags": 20, "layer_dims": [17, 13, 10, 10, 13, 17], "cv_folds": 3,
              "epochs": 5, "weights_per_member": 1582}
    useful = flops.job_useful_fit_flops(config, 1, 12961)
    assert useful == 3.0 * 2964 * (3241 + 6481 + 9721 + 12961) * 5
    least = flops.kernel_least_seconds(
        config, 1000, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    )
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(4.0 * (40000 + 1582) / 819e9)


def test_percentiles():
    values = list(range(1, 101))
    assert median(values) == 50.5
    assert percentile(values, 99) == pytest.approx(np.percentile(values, 99))
    assert percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)
