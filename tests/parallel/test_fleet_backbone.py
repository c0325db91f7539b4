"""A toy backbone machine on the normal path: ``build-fleet`` (fetch, CV
folds, thresholds, final fit, dump), ``serializer.load``,
``model.predict`` and the server's ``/prediction``; what its size forces
in the planner and the trainer; and the LSTM's fit, bit for bit what the
masked epoch loop gave."""

import json
import os
import re

import jax
import numpy as np
import pandas as pd
import pytest
import yaml
from werkzeug.test import Client

from gordo_tpu import serializer
from gordo_tpu.cli import gordo_tpu_cli
from gordo_tpu.models.factories import lfm2_moe, lstm_model
from gordo_tpu.models.training import FitConfig, build_raw_windowed_fit_fn
from gordo_tpu.ops.windows import window_targets
from gordo_tpu.parallel import FleetTrainer, WindowedFleetMember
from gordo_tpu.parallel.fleet import _fleet_windowed_fit_program
from gordo_tpu.planner import packing
from gordo_tpu.server import build_app
from gordo_tpu.telemetry.recorder import reset_seen_programs

PROJECT = "backbone-proj"
REVISION = "1700000000027"
LOOKBACK = 100
TOY = dict(
    kind="lfm2_moe", lookback_window=LOOKBACK,
    layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=48, moe_intermediate_size=24, num_experts=8,
    experts_held=2, num_experts_per_tok=2, epochs=2, batch_size=32,
)
TAGS = [f"bb-{i}" for i in range(4)]
MACHINES = ("compressor-a", "compressor-b")


#: ``kind: keye_vl2`` at toy widths: an indexer that keeps 24 of 100 rows
TOY_SPARSE = dict(
    kind="keye_vl2", lookback_window=LOOKBACK, num_hidden_layers=2, hidden_size=32, head_dim=16,
    num_attention_heads=4, num_key_value_heads=2, moe_intermediate_size=24, num_experts=8,
    experts_held=2, num_experts_per_tok=2, epochs=2, batch_size=32,
    sa_config=dict(indexer_head_dim=8, indexer_num_heads=8, topk=24, q_chunk_size=32, kv_chunk_size=32),
)


#: ``kind: laguna`` at toy widths: full, sliding, sliding, full; a window
#: of 24 of 100 rows in tiles of 32, heads of two counts, a shared expert
TOY_BANDED = dict(
    kind="laguna", lookback_window=LOOKBACK, num_hidden_layers=4,
    layer_types=["full_attention", "sliding_attention", "sliding_attention", "full_attention"],
    num_attention_heads_per_layer=[6, 8, 8, 6], hidden_size=32, head_dim=16, num_key_value_heads=2,
    intermediate_size=48, moe_intermediate_size=24, shared_expert_intermediate_size=20, num_experts=8,
    experts_held=2, num_experts_per_tok=2, sliding_window=24, epochs=2, batch_size=32,
)


def build_fleet(root, estimator, machines):
    document = {
        "project_name": PROJECT,
        "machines": [{
            "name": name,
            "model": {"gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
                "base_estimator": {"sklearn.pipeline.Pipeline": {"steps": [
                    "sklearn.preprocessing.MinMaxScaler",
                    {"gordo_tpu.models.JaxBackboneForecast": dict(estimator)},
                ]}}
            }},
            "dataset": {
                "type": "TimeSeriesDataset",
                "data_provider": {"type": "RandomDataProvider", "min_size": 145, "max_size": 145},
                "train_start_date": "2020-01-01T00:00:00+00:00",
                "train_end_date": "2020-01-02T00:00:00+00:00",
                "resolution": "10min",
                "tag_list": TAGS,
            },
        } for name in machines],
    }
    config_path = str(root.parent / "machines.yaml")
    with open(config_path, "w") as f:
        yaml.safe_dump(document, f)
    # ``compile`` on a ``device_program`` span is "first seen in this
    # process" (telemetry/recorder.py:seen_program), and a worker of the
    # suite may have built the same toy spec in another file before this
    # one (``test_fold_params_on_device.py`` imports ``TOY``): the tests
    # here count one compile a build from a clean slate, whatever the
    # worker ran first
    reset_seen_programs()
    try:
        gordo_tpu_cli.main(["build-fleet", config_path, str(root)], standalone_mode=False)
        code = 0
    except SystemExit as exc:
        code = int(exc.code or 0)
    return code, str(root)


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """Two toy machines of one configuration built by the ``build-fleet``
    command: 145 rows, 45 windows of 100 rows, three folds and the final
    fit each. One configuration is one spec, and a spec without a member
    axis shares no program: every fit and every score runs one machine."""
    return build_fleet(tmp_path_factory.mktemp("backbone") / REVISION, TOY, MACHINES)


def rows(n):
    rng = np.random.RandomState(1)
    return pd.DataFrame(
        rng.uniform(0, 1, (n, len(TAGS))), columns=TAGS,
        index=pd.date_range("2021-01-01", periods=n, freq="10min", tz="UTC"),
    )


def test_build_fleet_builds_the_toy_machines(built):
    code, root = built
    assert code == 0
    with open(os.path.join(root, "build_status.json")) as f:
        status = json.load(f)
    assert status["state"] == "complete" and status["machines"]["completed"] == 2
    assert not any(status["robustness"].values())
    assert {"data_fetch", "cv_train", "cv_predict", "final_fit", "dump"} <= set(status["phases"])
    # three folds and the final fit a machine: one member a program, its
    # counters kept (fold-major: each fold's two machines, then the fits)
    counters = status["fit_counters"]
    assert len(counters) == 8 and all(c["members"] == 1 for c in counters)
    for c in counters:
        assert c["num_experts"] == 8 and c["experts_held"] == 2
        assert len(c["router_tokens"]) == 2 and len(c["router_tokens"][0]) == 8
        assert c["pairs_here"] == [sum(layer[:2]) for layer in c["router_tokens"]]
    # 45 windows: the folds train 12, 23 and 34 of them, the final fit 45;
    # of an epoch's 3 steps of 32 slots, 1, 1, 2 and 2 hold a window and
    # run; a step of padding alone is skipped and counts nothing, and in
    # a step that runs the slots of padding route nothing: the pairs in
    # all are those of the windows trained
    a_window = LOOKBACK * 2  # tokens x experts per token
    assert sorted(c["steps_run"] for c in counters) == [2, 2, 2, 2, 4, 4, 4, 4]  # 2 epochs
    assert sorted(c["pairs_total"][0] for c in counters) == [
        2 * windows * a_window for windows in (12, 12, 23, 23, 34, 34, 45, 45)
    ]
    assert all(c["pairs_total"][0] == c["pairs_total"][1] for c in counters)
    assert all(sum(layer) == c["pairs_total"][0] for c in counters for layer in c["router_tokens"])
    assert all(c["stacked_samples"] == 96 for c in counters)
    with open(os.path.join(root, "build_trace.jsonl")) as f:
        spans = [json.loads(line) for line in f]
    fits = [s["attributes"] for s in spans
            if s["name"] == "device_program" and "fit" in s["attributes"]["program"]]
    assert len(fits) == 8 and sum(bool(a["compile"]) for a in fits) == 1  # one compile, run eight times
    assert all(a["tokens_per_step"] == 32 * LOOKBACK and a["params"] > 0 for a in fits)
    assert all(set(a["fit_counters"]) >= {"router_tokens", "pairs_here", "steps_run"} for a in fits)
    scores = [s["attributes"] for s in spans
              if s["name"] == "device_program" and "predict" in s["attributes"]["program"]]
    assert len(scores) == 6 and all(a["members"] == 1 for a in scores)  # a fold's machines score alone
    metadata = serializer.load_metadata(os.path.join(root, "compressor-a"))
    model_meta = metadata["metadata"]["build_metadata"]["model"]
    assert model_meta["model_offset"] == LOOKBACK  # lookahead 1
    meta = model_meta["model_meta"]
    assert np.isfinite(meta["aggregate-threshold"]) and len(meta["history"]["loss"]) == 2
    assert all(np.isfinite(t) for t in meta["feature-thresholds"])
    splits = model_meta["cross_validation"]["splits"]
    assert len([k for k in splits if k.endswith("train-start")]) == 3


def test_the_artifact_loads_and_predicts(built):
    _, root = built
    model = serializer.load(os.path.join(root, "compressor-a"))
    X = rows(LOOKBACK + 6)
    prediction = np.asarray(model.predict(X))
    assert prediction.shape == (6, len(TAGS)) and np.isfinite(prediction).all()
    frame = model.anomaly(X, X)
    assert len(frame) == 6 and np.isfinite(frame["total-anomaly-scaled"].to_numpy()).all()
    estimator = model.base_estimator.steps[-1][1]
    assert type(estimator).__name__ == "JaxBackboneForecast"
    assert estimator.spec_.experts_held == 2 and estimator.spec_.windowed
    assert all(isinstance(leaf, np.ndarray) for leaf in jax.tree_util.tree_leaves(estimator.params_))


def test_the_server_answers_prediction_from_it(built):
    from tests.server.conftest import temp_env_vars

    _, root = built
    X = rows(LOOKBACK + 5)
    values = {tag: {ts.isoformat(): float(v) for ts, v in X[tag].items()} for tag in TAGS}
    with temp_env_vars(MODEL_COLLECTION_DIR=root):
        client = Client(build_app())
        for route in ("prediction", "anomaly/prediction"):
            resp = client.post(
                f"/gordo/v0/{PROJECT}/compressor-a/{route}", json={"X": values, "y": values}
            )
            assert resp.status_code == 200, resp.text
            data = json.loads(resp.data)["data"]
            assert len(next(iter(data["model-output"].values()))) == 5
        # the fleet route over both machines of the one spec: each scores
        # in a program of its own, and gives what its own route gave
        resp = client.post(
            f"/gordo/v0/{PROJECT}/prediction/fleet",
            json={"X": {name: values for name in MACHINES}},
        )
        assert resp.status_code == 200, resp.text
        answered = json.loads(resp.data)["data"]
        assert set(answered) == set(MACHINES)
        for name in MACHINES:
            alone = client.post(f"/gordo/v0/{PROJECT}/{name}/prediction", json={"X": values})
            want = pd.DataFrame(json.loads(alone.data)["data"]["model-output"])
            got = pd.DataFrame(answered[name]["model-output"])
            assert got.shape == (5, len(TAGS))
            np.testing.assert_allclose(got.to_numpy(), want.to_numpy(), rtol=1e-5)


def test_a_sparse_attention_backbone_takes_the_same_path(tmp_path_factory):
    """``kind: keye_vl2`` through ``build-fleet``, the artifact and the
    server, as ``lfm2_moe`` goes: its fits carry the selection's counters
    beside the router's, and the indexer's objective is in their loss."""
    from gordo_tpu.telemetry.trace_analysis import build_breakdown, render_analysis
    from tests.server.conftest import temp_env_vars

    code, root = build_fleet(tmp_path_factory.mktemp("sparse") / REVISION, TOY_SPARSE, ("turbine-k",))
    assert code == 0
    with open(os.path.join(root, "build_status.json")) as f:
        status = json.load(f)
    assert status["state"] == "complete" and status["machines"]["completed"] == 1
    assert not any(status["robustness"].values())
    counters = status["fit_counters"]
    assert len(counters) == 4 and all(c["members"] == 1 for c in counters)
    kept = sum(min(t + 1, 24) for t in range(LOOKBACK))
    for c, windows in zip(sorted(counters, key=lambda c: c["pairs_total"][0]), (12, 23, 34, 45)):
        assert c["index_topk"] == 24 and c["pairs_total"] == [2 * windows * LOOKBACK * 2] * 2
        assert c["keys_selected"] == [2.0 * windows * kept] * 2  # two epochs, a layer each
        assert c["keys_causal"] == [2.0 * windows * LOOKBACK * (LOOKBACK + 1) / 2] * 2
        assert len(c["indexer_kl"]) == 2 and all(0 < kl < 10 * c["steps_run"] for kl in c["indexer_kl"])
        assert c["pairs_here"] == [sum(layer[:2]) for layer in c["router_tokens"]]
    with open(os.path.join(root, "build_trace.jsonl")) as f:
        spans = [json.loads(line) for line in f]
    fits = [s["attributes"] for s in spans
            if s["name"] == "device_program" and "fit" in s["attributes"]["program"]]
    assert len(fits) == 4 and sum(bool(a["compile"]) for a in fits) == 1
    assert all(set(a["fit_counters"]) >= {"keys_selected", "keys_causal", "indexer_kl", "index_topk"} for a in fits)
    # 100 rows in blocks of 32 that keep 24: every block of the four searches, in fit and predict programs alike
    programs = [s["attributes"] for s in spans if s["name"] == "device_program"]
    assert len(programs) > len(fits) and all(a["selection_blocks_searched"] == 4 for a in programs)
    assert all("selection_blocks_searched" in a["fit_counters"] for a in fits)
    assert all(c["selection_blocks_searched"] == 4 for c in counters)
    rendered = render_analysis({"trace": "t", "spans_read": len(spans), "build_breakdown": build_breakdown(spans)})
    assert "  program fleet_windowed_fit [validation_slots=0, selection_blocks_searched=4]" in rendered
    assert re.search(
        r"  program fleet_windowed_predict\w* \[members=(\d+), params_resident_members=\1, selection_blocks_searched=4\]",
        rendered,
    )
    model = serializer.load(os.path.join(root, "turbine-k"))
    X = rows(LOOKBACK + 6)
    prediction = np.asarray(model.predict(X))
    assert prediction.shape == (6, len(TAGS)) and np.isfinite(prediction).all()
    estimator = model.base_estimator.steps[-1][1]
    assert estimator.spec_.layer_ops == ("sparse_attention",) * 2 and estimator.spec_.router == "softmax"
    assert "expert_bias" not in estimator.params_["layer_0"]["moe"]
    loss, norms = estimator.training_loss_and_grad_norms(
        model.base_estimator.steps[0][1].transform(X), X.to_numpy()
    )
    assert np.isfinite(loss) and norms["layer_1"]["indexer"]["wq"] > 0
    values = {tag: {ts.isoformat(): float(v) for ts, v in X[tag].items()} for tag in TAGS}
    with temp_env_vars(MODEL_COLLECTION_DIR=root):
        client = Client(build_app())
        alone = client.post(f"/gordo/v0/{PROJECT}/turbine-k/prediction", json={"X": values})
        assert alone.status_code == 200, alone.text
        want = pd.DataFrame(json.loads(alone.data)["data"]["model-output"])
        np.testing.assert_allclose(want.to_numpy(), prediction, rtol=1e-4, atol=1e-5)
        fleet = client.post(f"/gordo/v0/{PROJECT}/prediction/fleet", json={"X": {"turbine-k": values}})
        assert fleet.status_code == 200, fleet.text
        got = pd.DataFrame(json.loads(fleet.data)["data"]["turbine-k"]["model-output"])
        np.testing.assert_allclose(got.to_numpy(), want.to_numpy(), rtol=1e-5)


def test_a_banded_attention_backbone_takes_the_same_path(tmp_path_factory, monkeypatch):
    """``kind: laguna`` through ``build-fleet``, the artifact and the
    server, as the other two go: its fits carry the band's counters
    beside the router's, a row a layer."""
    from gordo_tpu.models import backbone
    from tests.server.conftest import temp_env_vars

    monkeypatch.setattr(backbone, "ATTENTION_TILE", 32)  # the program's constant is 512 rows: a toy's 100 take 32
    code, root = build_fleet(tmp_path_factory.mktemp("banded") / REVISION, TOY_BANDED, ("pump-l",))
    assert code == 0
    with open(os.path.join(root, "build_status.json")) as f:
        status = json.load(f)
    assert status["state"] == "complete" and status["machines"]["completed"] == 1
    assert not any(status["robustness"].values())
    counters = status["fit_counters"]
    assert len(counters) == 4 and all(c["members"] == 1 for c in counters)
    full, sliding = LOOKBACK * (LOOKBACK + 1) / 2, sum(min(t + 1, 24) for t in range(LOOKBACK))
    for c, windows in zip(sorted(counters, key=lambda c: c["pairs_total"][0]), (12, 23, 34, 45)):
        trained = 2.0 * windows  # two epochs
        assert c["pairs_total"] == [2 * windows * LOOKBACK * 2] * 3 and "index_topk" not in c
        assert c["pairs_attended"] == [trained * full, trained * sliding, trained * sliding, trained * full]
        # tiles of 32 over 100 rows: 10 up to the diagonal, 7 in a band that reaches one tile back
        assert c["pairs_multiplied"] == [trained * n * 32 * 32 for n in (10, 7, 7, 10)]
        assert c["pairs_here"] == [sum(layer[:2]) for layer in c["router_tokens"]]
    with open(os.path.join(root, "build_trace.jsonl")) as f:
        spans = [json.loads(line) for line in f]
    fits = [s["attributes"] for s in spans
            if s["name"] == "device_program" and "fit" in s["attributes"]["program"]]
    assert len(fits) == 4 and sum(bool(a["compile"]) for a in fits) == 1
    assert all(set(a["fit_counters"]) >= {"pairs_attended", "pairs_multiplied", "pairs_here"} for a in fits)
    model = serializer.load(os.path.join(root, "pump-l"))
    X = rows(LOOKBACK + 6)
    prediction = np.asarray(model.predict(X))
    assert prediction.shape == (6, len(TAGS)) and np.isfinite(prediction).all()
    estimator = model.base_estimator.steps[-1][1]
    assert estimator.spec_.layer_ops == tuple(TOY_BANDED["layer_types"])
    assert estimator.spec_.layer_heads == (6, 8, 8, 6) and estimator.spec_.routed_scaling_factor == 2.5
    assert estimator.params_["layer_1"]["attn"]["gate"].shape == (32, 8)
    assert estimator.params_["layer_3"]["moe"]["shared"]["w1"].shape == (32, 20)
    loss, norms = estimator.training_loss_and_grad_norms(
        model.base_estimator.steps[0][1].transform(X), X.to_numpy()
    )
    assert np.isfinite(loss) and norms["layer_2"]["attn"]["gate"] > 0 and norms["layer_3"]["moe"]["shared"]["w2"] > 0
    values = {tag: {ts.isoformat(): float(v) for ts, v in X[tag].items()} for tag in TAGS}
    with temp_env_vars(MODEL_COLLECTION_DIR=root):
        client = Client(build_app())
        alone = client.post(f"/gordo/v0/{PROJECT}/pump-l/prediction", json={"X": values})
        assert alone.status_code == 200, alone.text
        want = pd.DataFrame(json.loads(alone.data)["data"]["model-output"])
        np.testing.assert_allclose(want.to_numpy(), prediction, rtol=1e-4, atol=1e-5)


#: ``kind: smallthinker`` at toy widths: full without positions, then three
#: sliding layers with rotary; a band of 30 of 100 rows in tiles of 8, 14
#: heads over 2 key/value heads, a router on the layer's input, relu experts
TOY_PREROUTED = dict(
    kind="smallthinker", lookback_window=LOOKBACK, num_hidden_layers=4, hidden_size=32, head_dim=16,
    num_attention_heads=14, num_key_value_heads=2, moe_ffn_hidden_size=24, moe_num_primary_experts=8,
    experts_held=2, moe_num_active_primary_experts=3, sliding_window_size=30, epochs=2, batch_size=32,
)


def test_a_prerouted_backbone_takes_the_same_path(tmp_path_factory, monkeypatch):
    """``kind: smallthinker`` through ``build-fleet``, the artifact and
    ``gordo-tpu trace``, as the other three go: its fits carry the band's
    counters and the gate's beside the router's, a row a layer."""
    from gordo_tpu.models import backbone
    from gordo_tpu.telemetry.trace_analysis import build_breakdown, render_analysis

    monkeypatch.setattr(backbone, "ATTENTION_TILE", 8)  # the program's constant is 512 rows: a toy's 100 take 8
    code, root = build_fleet(tmp_path_factory.mktemp("prerouted") / REVISION, TOY_PREROUTED, ("pump-s",))
    assert code == 0
    with open(os.path.join(root, "build_status.json")) as f:
        status = json.load(f)
    assert status["state"] == "complete" and status["machines"]["completed"] == 1
    assert not any(status["robustness"].values())
    counters = status["fit_counters"]
    assert len(counters) == 4 and all(c["members"] == 1 for c in counters)
    full, sliding = LOOKBACK * (LOOKBACK + 1) / 2, sum(min(t + 1, 30) for t in range(LOOKBACK))
    for c, windows in zip(sorted(counters, key=lambda c: c["pairs_total"][0]), (12, 23, 34, 45)):
        trained = 2.0 * windows  # two epochs
        assert c["pairs_total"] == [2 * windows * LOOKBACK * 3] * 4 and "index_topk" not in c
        assert c["pairs_attended"] == [trained * full] + [trained * sliding] * 3
        # tiles of 8 over 100 rows: 91 up to the diagonal, 55 in a band that reaches four tiles back
        assert c["pairs_multiplied"] == [trained * n * 8 * 8 for n in (91, 55, 55, 55)]
        assert c["pairs_here"] == [sum(layer[:2]) for layer in c["router_tokens"]]
        assert c["gate_total"] == [24.0 * pairs for pairs in c["pairs_here"]]
        assert 0 < sum(c["gate_active"]) < sum(c["gate_total"])
    with open(os.path.join(root, "build_trace.jsonl")) as f:
        spans = [json.loads(line) for line in f]
    fits = [s["attributes"] for s in spans
            if s["name"] == "device_program" and "fit" in s["attributes"]["program"]]
    assert len(fits) == 4 and sum(bool(a["compile"]) for a in fits) == 1
    assert all(set(a["fit_counters"]) >= {"gate_active", "gate_total", "pairs_attended", "pairs_here"} for a in fits)
    found = build_breakdown(spans)
    rendered = render_analysis({"trace": "t", "spans_read": len(spans), "build_breakdown": found})
    last = max(fits, key=lambda a: a["pairs_total"][0])  # the final fit, a phase of its own
    share = 100.0 * sum(last["gate_active"]) / sum(last["gate_total"])
    assert f"  program fleet_windowed_fit [validation_slots=0, gate_active_pct={share:.1f}]" in rendered
    model = serializer.load(os.path.join(root, "pump-s"))
    X = rows(LOOKBACK + 6)
    prediction = np.asarray(model.predict(X))
    assert prediction.shape == (6, len(TAGS)) and np.isfinite(prediction).all()
    estimator = model.base_estimator.steps[-1][1]
    assert estimator.spec_.layer_ops == ("full_attention",) + ("sliding_attention",) * 3
    assert (estimator.spec_.router_input, estimator.spec_.expert_activation) == ("layer_input", "relu")
    assert estimator.params_["layer_1"]["attn"]["wq"].shape == (32, 14 * 16)
    loss, norms = estimator.training_loss_and_grad_norms(
        model.base_estimator.steps[0][1].transform(X), X.to_numpy()
    )
    # (a layer none of whose pairs came here gives its router nothing: the sum over the layers)
    assert np.isfinite(loss) and norms["layer_0"]["attn"]["wq"] > 0
    assert sum(norms[f"layer_{i}"]["moe"]["router"] for i in range(4)) > 0


def series(n=150, f=4, seed=0):
    return np.random.RandomState(seed).rand(n, f).astype(np.float32)


def test_a_member_without_a_member_axis_trains_alone():
    spec = lfm2_moe(4, **{k: v for k, v in TOY.items() if k not in ("kind", "epochs", "batch_size")})
    lstm = lstm_model(4, lookback_window=LOOKBACK, encoding_dim=(4,), encoding_func=("tanh",),
                      decoding_dim=(4,), decoding_func=("tanh",))
    assert packing.trains_alone(spec) and not packing.trains_alone(lstm)
    config = FitConfig(epochs=1, batch_size=32, shuffle=False)

    def members(s):
        X = series()
        return [WindowedFleetMember(name=f"m{i}", spec=s, series=X,
                                    targets=window_targets(X, LOOKBACK, 1), seed=i)
                for i in range(3)]

    for strategy in packing.STRATEGIES:  # the toy's state is far under the cap: the axis decides
        alone = packing.plan_train_buckets(members(spec), config, strategy=strategy)
        assert [len(b.members) for b in alone] == [1, 1, 1], strategy
        assert len({b.bucket_id for b in alone}) == 3 and len({b.n_padded for b in alone}) == 1
        together = packing.plan_train_buckets(members(lstm), config, strategy=strategy)
        assert [len(b.members) for b in together] == [3], strategy


def test_a_member_larger_than_the_cap_trains_alone(monkeypatch):
    lstm = lstm_model(4, lookback_window=8)
    assert not packing.trains_alone(lstm)
    monkeypatch.setenv(packing.HBM_CAP_ENV, str(1 << 20))  # 1 MiB: 1.1 M weights x 16 bytes exceed it
    assert packing.trains_alone(lstm_model(50, lookback_window=8))
    # the published cut: 7.59 GB of state against the 4 GiB cap
    monkeypatch.delenv(packing.HBM_CAP_ENV)
    big = lfm2_moe(50, layer_types=("conv", "full_attention", "conv", "conv", "conv"),
                   num_dense_layers=1, experts_held=8)
    from gordo_tpu.planner.costmodel import spec_state_bytes

    assert spec_state_bytes(big) == 16 * 474_472_626 > packing.hbm_cap_bytes()


def test_the_trainer_refuses_a_stacked_bucket_of_them_and_runs_one():
    spec = lfm2_moe(4, **{k: v for k, v in TOY.items() if k not in ("kind", "epochs", "batch_size")})
    config = FitConfig(epochs=1, batch_size=32, shuffle=False)
    X = series()
    trainer = FleetTrainer()
    assert trainer._mesh_for(spec).devices.size == 1
    members = [WindowedFleetMember(name=f"m{i}", spec=spec, series=X,
                                   targets=window_targets(X, LOOKBACK, 1), seed=i) for i in range(2)]
    with pytest.raises(ValueError, match="one member a program"):
        trainer._train_windowed_bucket(spec, 170, LOOKBACK, members, config)
    results = trainer.train(members, config)
    assert [r.name for r in results] == ["m0", "m1"] and all(r.error is None for r in results)
    assert all(np.isfinite(r.history.history["loss"][-1]) for r in results)
    # fit_single's derivation: the same seed gives the same member
    again = trainer.train(members[:1], config)[0]
    for a, b in zip(jax.tree_util.tree_leaves(again.params), jax.tree_util.tree_leaves(results[0].params)):
        assert np.array_equal(a, b)


def test_a_short_history_cuts_its_folds_over_the_target_rows(built):
    """145 rows under a lookback of 100: no row fold of TimeSeriesSplit(3)
    (36 rows) holds a window, so the folds are over rows 100..144."""
    _, root = built
    metadata = serializer.load_metadata(os.path.join(root, "compressor-a"))
    cv = metadata["metadata"]["build_metadata"]["model"]["cross_validation"]
    starts = sorted(v for k, v in cv["splits"].items() if k.endswith("test-start"))
    assert len(starts) == 3
    first_target = pd.Timestamp("2020-01-01T00:00:00+00:00") + pd.Timedelta(minutes=10 * LOOKBACK)
    assert all(pd.Timestamp(s) > first_target for s in starts)
    scores = cv["scores"]
    assert all(np.isfinite(v["fold-mean"]) for v in scores.values())


def test_an_lstm_with_a_short_history_takes_the_same_rule_and_a_long_one_keeps_row_folds(tmp_path, caplog):
    """The rule is the windowed path's, not the backbone's: an LSTM with
    a lookback of 100 over 145 rows has its folds over the target rows
    (and says so in its metadata and in the log); the same model over 600
    rows, where a row fold of 150 holds 51 windows, keeps row folds. And
    over 117 rows, 17 windows, a fold scores 4 rows, fewer than the six a
    threshold's run takes: its thresholds are the minimum over the four,
    said in the log, and not the NaN that ``rolling(6)`` of four rows is."""
    from gordo_tpu.models.anomaly.diff import threshold_run

    assert [threshold_run(n) for n in (0, 1, 4, 6, 7, 345)] == [1, 1, 4, 6, 6, 6]
    lstm = {"gordo_tpu.models.JaxLSTMForecast": dict(
        kind="lstm_model", lookback_window=LOOKBACK, encoding_dim=[4], encoding_func=["tanh"],
        decoding_dim=[4], decoding_func=["tanh"], epochs=1, batch_size=32,
    )}

    def machine(name, rows, end):
        return {
            "name": name,
            "model": {"gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
                "base_estimator": {"sklearn.pipeline.Pipeline": {"steps": [
                    "sklearn.preprocessing.MinMaxScaler", lstm,
                ]}}
            }},
            "dataset": {
                "type": "TimeSeriesDataset",
                "data_provider": {"type": "RandomDataProvider", "min_size": rows, "max_size": rows},
                "train_start_date": "2020-01-01T00:00:00+00:00", "train_end_date": end,
                "resolution": "10min", "tag_list": TAGS,
            },
        }

    document = {"project_name": PROJECT, "machines": [
        machine("short", 145, "2020-01-02T00:00:00+00:00"),
        machine("long", 600, "2020-01-05T03:50:00+00:00"),
        machine("brief", 117, "2020-01-01T19:20:00+00:00"),
    ]}
    config_path = str(tmp_path / "machines.yaml")
    with open(config_path, "w") as f:
        yaml.safe_dump(document, f)
    root = str(tmp_path / "out")
    with caplog.at_level("WARNING", logger="gordo_tpu.parallel.fleet_build"):
        gordo_tpu_cli.main(["build-fleet", config_path, root], standalone_mode=False)
    cvs = {
        name: serializer.load_metadata(os.path.join(root, name))["metadata"]["build_metadata"][
            "model"]["cross_validation"]
        for name in ("short", "long", "brief")
    }
    brief = serializer.load_metadata(os.path.join(root, "brief"))["metadata"]["build_metadata"]["model"]["model_meta"]
    assert np.isfinite(brief["aggregate-threshold"]) and np.all(np.isfinite(brief["feature-thresholds"]))
    assert cvs["brief"]["splits"]["folds-over"] == "target-rows"
    # ... and the metadata of the model that serves them says so; no other model's does
    assert (brief["thresholds-degraded"], brief["threshold-run-rows"]) == (True, 4)
    for name in ("short", "long"):
        meta = serializer.load_metadata(os.path.join(root, name))["metadata"]["build_metadata"]["model"]["model_meta"]
        assert "thresholds-degraded" not in meta and "threshold-run-rows" not in meta
    said = [r.getMessage() for r in caplog.records if "fewer than the 6" in r.getMessage()]
    assert len(said) == 3 and all(m.startswith("brief: fold") and "scored 4 rows" in m for m in said)
    assert cvs["short"]["splits"]["folds-over"] == "target-rows"
    assert "folds-over" not in cvs["long"]["splits"]
    assert [r for r in caplog.records if "short: no CV fold" in r.getMessage()]
    assert not [r for r in caplog.records if "long: no CV fold" in r.getMessage()]
    first_target = pd.Timestamp("2020-01-01T00:00:00+00:00") + pd.Timedelta(minutes=10 * LOOKBACK)  # lookahead 1
    for name, cv in cvs.items():
        assert all(np.isfinite(v["fold-mean"]) for v in cv["scores"].values()), name
        starts = [pd.Timestamp(v) for k, v in cv["splits"].items() if k.endswith("train-start")]
        # row folds train from the first row, target folds from the first target row
        assert all(s == (pd.Timestamp("2020-01-01T00:00:00+00:00") if name == "long" else first_target) for s in starts)


def test_the_lstm_fit_is_bit_for_bit_what_the_masked_loop_gave():
    """Without early stopping the epoch loop no longer masks the update
    with ``stopped`` (a second copy of the state); the masked loop, which
    early stopping keeps, computes the same numbers: an early stopping
    that can never stop gives the program as it was."""
    spec = lstm_model(3, lookback_window=6, encoding_dim=(8,), encoding_func=("tanh",),
                      decoding_dim=(8,), decoding_func=("tanh",))
    X = series(80, 3, seed=2)
    targets = window_targets(X, 6, 0)
    nv = 96
    order = np.zeros(nv, np.int32)
    order[: len(targets)] = np.arange(len(targets))
    wtr = (np.arange(nv) < 60).astype(np.float32)
    wval = ((np.arange(nv) >= 60) & (np.arange(nv) < len(targets))).astype(np.float32)
    params = spec.init_fn()(jax.random.PRNGKey(1), spec)
    rng = jax.random.PRNGKey(2)
    outs = {}
    for name, es in (("plain", None), ("masked", ("loss", 10**9, -1e9, False))):
        config = FitConfig(epochs=3, batch_size=16, shuffle=True, early_stopping=es)
        fit = jax.jit(build_raw_windowed_fit_fn(spec, config))
        opt_state = spec.optimizer.to_optax().init(params)
        outs[name] = jax.device_get(fit(params, opt_state, X, targets, order, wtr, wval, rng))
    assert len(outs["plain"]) == len(outs["masked"]) == 5  # no sixth output: the LSTM has no counters
    for a, b in zip(jax.tree_util.tree_leaves(outs["plain"]), jax.tree_util.tree_leaves(outs["masked"])):
        assert np.array_equal(a, b)
    assert int(outs["plain"][4]) == 3
    # and the fleet's program donates its state without changing a number
    config = FitConfig(epochs=3, batch_size=16, shuffle=True)
    stack = lambda t: jax.tree_util.tree_map(lambda a: np.asarray(a)[None], t)  # noqa: E731
    fleet_out = jax.device_get(_fleet_windowed_fit_program(spec, config)(
        jax.tree_util.tree_map(jax.numpy.asarray, stack(params)),
        jax.tree_util.tree_map(jax.numpy.asarray, stack(spec.optimizer.to_optax().init(params))),
        X[None], targets[None], order[None], wtr[None], wval[None], np.asarray(rng)[None],
    ))
    for a, b in zip(jax.tree_util.tree_leaves(fleet_out[0]), jax.tree_util.tree_leaves(outs["plain"][0])):
        np.testing.assert_allclose(a[0], b, rtol=0, atol=1e-6)


def test_a_skipped_padding_step_is_the_masked_step(monkeypatch):
    """A one-member program skips a batch of padding alone behind a
    branch; masked, the same batch is a no-op update and a zero
    contribution: the same parameters and losses, and counters that
    count the steps that ran."""
    from gordo_tpu.models import training

    spec = lfm2_moe(4, **{k: v for k, v in TOY.items() if k not in ("kind", "epochs", "batch_size")})
    X = series(150, 4, seed=3)
    targets = window_targets(X, LOOKBACK, 1)  # 50 windows
    order = np.zeros(128, np.int32)
    order[: len(targets)] = np.arange(len(targets))
    wtr = (np.arange(128) < 40).astype(np.float32)  # 2 of 4 steps hold a window
    wval = np.zeros(128, np.float32)
    config = FitConfig(epochs=2, batch_size=32, shuffle=False)
    params = spec.init_fn()(jax.random.PRNGKey(1), spec)
    outs = {}
    for name in ("skipping", "masked"):
        training.build_raw_windowed_fit_fn.cache_clear()
        if name == "masked":
            monkeypatch.setattr(training, "_skipping_padding", lambda step: step)
        fit = jax.jit(training.build_raw_windowed_fit_fn(spec, config))
        opt_state = spec.optimizer.to_optax().init(params)
        outs[name] = jax.device_get(
            fit(params, opt_state, X, targets, order, wtr, wval, jax.random.PRNGKey(2))
        )
    training.build_raw_windowed_fit_fn.cache_clear()
    for a, b in zip(jax.tree_util.tree_leaves(outs["skipping"][:5]),
                    jax.tree_util.tree_leaves(outs["masked"][:5])):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6, equal_nan=True)
    # the 40 windows that count, 2 experts a token, either way: a slot
    # of padding routes nothing, in a step that runs or in one that is
    # masked
    for name in outs:
        assert outs[name][5]["pairs_total"].tolist() == [[40 * LOOKBACK * 2] * 2] * 2
        assert outs[name][5]["steps_run"].tolist() == [2, 2]


def test_large_leaves_are_fetched_as_they_are_and_one_member_is_not_copied(monkeypatch):
    from gordo_tpu.parallel import fleet
    from gordo_tpu.parallel.fleet import FleetResult, fetch_to_host, stack_member_params

    tree = {"big": jax.numpy.arange(4096, dtype=jax.numpy.float32).reshape(64, 64),
            "small": jax.numpy.ones((3,), jax.numpy.float32), "count": jax.numpy.array(7)}
    whole = fetch_to_host(tree)
    monkeypatch.setattr(fleet, "_COALESCE_MAX_LEAF_BYTES", 1024)
    split = fetch_to_host(tree)
    for key in tree:
        assert isinstance(split[key], np.ndarray) and np.array_equal(split[key], whole[key])
    leaf = np.arange(6, dtype=np.float32).reshape(2, 3)
    one = stack_member_params([FleetResult("m", {"w": leaf}, None)])
    assert one["w"].shape == (1, 2, 3) and np.shares_memory(one["w"], leaf)
    two = stack_member_params([FleetResult("a", {"w": leaf}, None), FleetResult("b", {"w": leaf}, None)])
    assert two["w"].shape == (2, 2, 3)
