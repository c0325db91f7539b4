"""
bfloat16 compute support: specs carry ``compute_dtype``; params and
activations run in bf16 while outputs, losses and thresholds stay
float32 (the dtype contract in models/nn.py). In the measured HBM-bound
tiny-model regime bf16 halves the bytes each training step re-reads —
the bench's fleet stage reports the realized speedup.

Correctness here is PARITY, not convergence: a bf16 model must answer
(tolerably) what the same-seed f32 model answers. The old assert —
"bf16 converges past 0.8 EV" — tracked the init seed, not the dtype
(CHANGES.md: it flipped between seeds with f32 scoring identically),
so it could fail on a healthy bf16 path and pass on a broken one. The
tolerance-based check (``gordo_tpu.serve.precision.recon_agreement``)
is the same math the serving precision-parity gate runs.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models.estimators import JaxAutoEncoder, JaxLSTMAutoEncoder
from gordo_tpu.models.factories import feedforward_hourglass, lstm_model
from gordo_tpu.models.training import FitConfig
from gordo_tpu.parallel import FleetMember, FleetTrainer
from gordo_tpu.serve.precision import recon_agreement

pytestmark = pytest.mark.precision


@pytest.fixture(scope="module")
def sine_data():
    rng = np.random.RandomState(0)
    t = np.linspace(0, 8 * np.pi, 400, dtype=np.float32)
    X = np.stack(
        [np.sin(t + phase) for phase in (0.0, 0.7, 1.4, 2.1)], axis=1
    ) + 0.05 * rng.standard_normal((400, 4)).astype(np.float32)
    return X


def test_factory_plumbs_compute_dtype():
    spec = feedforward_hourglass(8, compute_dtype="bfloat16")
    assert spec.compute_dtype == "bfloat16"
    lstm = lstm_model(8, lookback_window=4, compute_dtype="bfloat16")
    assert lstm.compute_dtype == "bfloat16"
    # default unchanged
    assert feedforward_hourglass(8).compute_dtype == "float32"


def test_bf16_estimator_trains_and_predicts_float32(sine_data):
    model = JaxAutoEncoder(
        kind="feedforward_hourglass",
        compute_dtype="bfloat16",
        epochs=30,
        batch_size=64,
        seed=1,
    )
    model.fit(sine_data, sine_data)
    assert model.spec_.compute_dtype == "bfloat16"
    # mixed precision: master params stay f32 (bf16 params drop most Adam
    # updates below the 8-bit-mantissa ULP — see models/nn.py)
    leaf = model.params_["dense_0"]["W"]
    assert jnp.asarray(leaf).dtype == jnp.float32
    out = model.predict(sine_data)
    # sklearn-facing output is full-precision numpy
    assert np.asarray(out).dtype == np.float32
    assert np.all(np.isfinite(out))


def test_bf16_tracks_f32_training_within_tolerance(sine_data):
    """The parity contract: same seed, same budget — the bf16 model's
    reconstructions agree with the f32 model's row for row within the
    precision-parity gate's tolerance (the shared ``recon_agreement``
    helper, NOT an absolute convergence bar that tracks seed luck)."""
    kwargs = dict(kind="feedforward_hourglass", epochs=30, batch_size=64, seed=1)
    f32 = JaxAutoEncoder(**kwargs).fit(sine_data, sine_data)
    bf16 = JaxAutoEncoder(compute_dtype="bfloat16", **kwargs).fit(
        sine_data, sine_data
    )
    report = recon_agreement(
        f32.predict(sine_data), bf16.predict(sine_data), rtol=0.1, atol=0.05
    )
    # training amplifies rounding differences over 30 epochs of updates,
    # so the tolerance is looser than the serving gate's (which compares
    # the SAME weights across dtypes); the overwhelming majority of rows
    # must still agree
    assert report["agreement"] >= 0.95, report
    # and the two models' answers stay in the same EV neighborhood —
    # relative parity, never an absolute convergence assert
    ev_f32 = f32.score(sine_data, sine_data)
    ev_bf16 = bf16.score(sine_data, sine_data)
    assert ev_bf16 > ev_f32 - 0.1, (ev_f32, ev_bf16)


def test_bf16_fleet_bucket(sine_data):
    spec = feedforward_hourglass(4, compute_dtype="bfloat16")
    members = [
        FleetMember(name=f"m{i}", spec=spec, X=sine_data, y=sine_data, seed=i)
        for i in range(3)
    ]
    results = FleetTrainer().train(members, FitConfig(epochs=5, batch_size=64))
    for result in results:
        assert np.isfinite(result.history.history["loss"][-1])


def test_bf16_lstm_trains(sine_data):
    model = JaxLSTMAutoEncoder(
        kind="lstm_model",
        lookback_window=6,
        compute_dtype="bfloat16",
        encoding_dim=(8,),
        encoding_func=("tanh",),
        decoding_dim=(8,),
        decoding_func=("tanh",),
        epochs=2,
    )
    model.fit(sine_data[:120], sine_data[:120])
    out = model.predict(sine_data[:60])
    assert np.asarray(out).dtype == np.float32
    assert np.all(np.isfinite(out))
