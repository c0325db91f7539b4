import numpy as np
import pytest

from gordo_tpu.models.factories import feedforward_symmetric
from gordo_tpu.models.training import FitConfig, fit_single
from gordo_tpu.parallel import FleetMember, FleetResult, FleetTrainer, make_mesh
from gordo_tpu.parallel.fleet import _round_up_pow2

SPEC = feedforward_symmetric(3, dims=(6, 3), funcs=("tanh", "tanh"))
CONFIG = FitConfig(epochs=3, batch_size=16, shuffle=False)


def _member(name, n, seed):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 3).astype(np.float32)
    return FleetMember(name=name, spec=SPEC, X=X, y=X.copy(), seed=seed)


def test_round_up_pow2():
    assert _round_up_pow2(100, 16) == 128
    assert _round_up_pow2(5, 16) == 16
    assert _round_up_pow2(128, 16) == 128
    assert _round_up_pow2(129, 16) == 256


def test_fleet_trains_ragged_members():
    """Members of different lengths in one bucket, all trained at once."""
    members = [_member(f"m{i}", n, i) for i, n in enumerate([50, 80, 100, 128])]
    trainer = FleetTrainer()
    results = trainer.train(members, CONFIG)
    assert [r.name for r in results] == ["m0", "m1", "m2", "m3"]
    for r in results:
        assert len(r.history.history["loss"]) == 3
        assert np.isfinite(r.history.history["loss"]).all()


def test_fleet_matches_single_model_training():
    """A fleet member must train to the same params as the single path when
    shapes align (same seed, same data, no padding difference)."""
    rng = np.random.RandomState(0)
    X = rng.rand(64, 3).astype(np.float32)  # 64 = already a pow2 multiple
    member = FleetMember(name="m", spec=SPEC, X=X, y=X.copy(), seed=7)
    fleet_result = FleetTrainer().train([member], CONFIG)[0]

    single_params, single_history = fit_single(SPEC, X, X.copy(), CONFIG, seed=7)
    import jax

    for fleet_leaf, single_leaf in zip(
        jax.tree_util.tree_leaves(fleet_result.params),
        jax.tree_util.tree_leaves(jax.device_get(single_params)),
    ):
        np.testing.assert_allclose(fleet_leaf, single_leaf, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        fleet_result.history.history["loss"],
        single_history.history["loss"],
        rtol=2e-4,
    )


def test_fleet_member_isolation():
    """A member's result must not depend on which other members share the
    fleet (same seed => same params)."""
    alone = FleetTrainer().train([_member("m", 64, 5)], CONFIG)[0]
    crowded = FleetTrainer().train(
        [_member("m", 64, 5)] + [_member(f"x{i}", 64, 50 + i) for i in range(3)],
        CONFIG,
    )[0]
    import jax

    for a, b in zip(
        jax.tree_util.tree_leaves(alone.params),
        jax.tree_util.tree_leaves(crowded.params),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_fleet_sharded_over_mesh():
    """8-device CPU mesh: the model axis shards without changing results."""
    import jax

    assert len(jax.devices()) == 8
    mesh = make_mesh()
    assert mesh.devices.shape == (8, 1)
    members = [_member(f"m{i}", 64, i) for i in range(8)]
    results = FleetTrainer(mesh=mesh).train(members, CONFIG)
    baseline = FleetTrainer(mesh=make_mesh(jax.devices()[:1])).train(members, CONFIG)
    for sharded, single_dev in zip(results, baseline):
        for a, b in zip(
            jax.tree_util.tree_leaves(sharded.params),
            jax.tree_util.tree_leaves(single_dev.params),
        ):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_fleet_data_axis_mesh():
    """models × data 2D mesh compiles and runs (GSPMD inserts collectives)."""
    mesh = make_mesh(data_parallelism=2)
    assert mesh.devices.shape == (4, 2)
    members = [_member(f"m{i}", 64, i) for i in range(4)]
    results = FleetTrainer(mesh=mesh).train(members, CONFIG)
    assert all(np.isfinite(r.history.history["loss"]).all() for r in results)


def test_mismatched_lengths_raise():
    with pytest.raises(ValueError):
        FleetMember(name="bad", spec=SPEC, X=np.zeros((10, 3)), y=np.zeros((9, 3)))


def test_non_pow2_data_axis_padding():
    """lcm padding: data axis 3 with batch 32 must not break batch reshape
    (regression for n_padded bumped to a non-multiple of batch_size)."""
    import jax

    mesh = make_mesh(jax.devices()[:6], data_parallelism=3)
    members = [_member(f"m{i}", 20, i) for i in range(2)]
    results = FleetTrainer(mesh=mesh).train(
        members, FitConfig(epochs=1, batch_size=32, shuffle=False)
    )
    assert all(np.isfinite(r.history.history["loss"]).all() for r in results)


def test_val_weights_without_train_weights():
    rng = np.random.RandomState(0)
    X = rng.rand(64, 3).astype(np.float32)
    val_mask = np.zeros(64, np.float32)
    val_mask[-16:] = 1.0
    member = FleetMember(
        name="m", spec=SPEC, X=X, y=X.copy(), val_weights=val_mask, seed=1
    )
    result = FleetTrainer().train([member], FitConfig(epochs=2, batch_size=16))[0]
    assert "val_loss" in result.history.history
    assert np.isfinite(result.history.history["val_loss"]).all()


def test_no_val_member_has_no_val_history():
    member = _member("m", 64, 2)
    result = FleetTrainer().train(
        [member], FitConfig(epochs=2, batch_size=16, validation_split=0.0)
    )[0]
    assert "val_loss" not in result.history.history


def test_host_prng_keys_bit_equal_jax():
    """host_prng_keys must match jax.random.PRNGKey bit-for-bit (the fleet
    staging path builds keys host-side to avoid per-member device round
    trips; any divergence would silently desync fleet vs fit_single RNG)."""
    import jax

    from gordo_tpu.parallel.fleet import host_prng_keys

    seeds = [0, 1, 7, 42, 2**31 - 1, 2**32 + 5, -1, -1234567]
    keys = host_prng_keys(seeds)
    for seed, key in zip(seeds, keys):
        expected = np.asarray(jax.random.PRNGKey(seed))
        np.testing.assert_array_equal(key, expected, err_msg=f"seed={seed}")


def test_fleet_retries_diverged_members():
    """Members with non-finite final loss are re-vmapped with a fresh seed
    (the chip-level analog of the reference DAG's pod retryStrategy)."""
    from unittest import mock

    from gordo_tpu.models.factories import feedforward_hourglass
    from gordo_tpu.models.training import FitConfig

    spec = feedforward_hourglass(4)
    X = np.random.RandomState(0).rand(32, 4).astype(np.float32)
    members = [
        FleetMember(name=f"m{i}", spec=spec, X=X, y=X, seed=i) for i in range(3)
    ]
    config = FitConfig(epochs=2, batch_size=16, shuffle=False)
    trainer = FleetTrainer()

    real = trainer._train_once(members, config)
    poisoned = [
        FleetResult(
            name=r.name,
            params=r.params,
            history=r.history,
            seed=r.seed,
        )
        for r in real
    ]
    poisoned[1].history.history["loss"] = [float("nan"), float("nan")]

    calls = []
    original = trainer._train_once

    def fake_train_once(ms, cfg, *on_device):
        calls.append([m.name for m in ms])
        if len(calls) == 1:
            return poisoned
        return original(ms, cfg, *on_device)

    with mock.patch.object(trainer, "_train_once", side_effect=fake_train_once):
        results = trainer.train(members, config)

    assert calls[0] == ["m0", "m1", "m2"]
    assert calls[1] == ["m1"]  # only the diverged member retried
    assert np.isfinite(results[1].history.history["loss"][-1])
    # retry reseeded: params differ from an identically-seeded fresh train
    assert results[1].name == "m1"
    # the retry is auditable: FleetResult records the reseed and count,
    # and the history params carry them into build metadata
    assert results[1].retries == 1
    assert results[1].seed == members[1].seed + 7919
    assert results[1].history.params["fleet_retry"] == {
        "retries": 1,
        "seed": members[1].seed + 7919,
    }
    # untouched members record their original seed and zero retries
    assert results[0].retries == 0 and results[0].seed == members[0].seed
    assert "fleet_retry" not in results[0].history.params


class TestFetchToHost:
    """Coalesced device→host fetch: values must round-trip exactly for
    any leaf count — including past _FLAT_CONCAT_MAX_LEAVES, where the
    coalescing proceeds in chunks rather than reverting to per-leaf
    transfers (the largest fleets are exactly where per-leaf round trips
    hurt most)."""

    def _tree(self, n_leaves, dtype=np.float32):
        import jax

        rng = np.random.RandomState(0)
        return {
            f"leaf_{i}": jax.device_put(
                rng.standard_normal((3, i % 5 + 1)).astype(dtype)
            )
            for i in range(n_leaves)
        }

    @pytest.mark.parametrize("n_leaves", [2, 7, 300])
    def test_round_trips_exactly(self, n_leaves):
        from gordo_tpu.parallel.fleet import fetch_to_host

        tree = self._tree(n_leaves)
        host = fetch_to_host(tree)
        assert set(host) == set(tree)
        for key, device_leaf in tree.items():
            np.testing.assert_array_equal(host[key], np.asarray(device_leaf))
            assert isinstance(host[key], np.ndarray)

    def test_mixed_dtypes_past_chunk_cap(self):
        import jax

        from gordo_tpu.parallel.fleet import _FLAT_CONCAT_MAX_LEAVES, fetch_to_host

        n = _FLAT_CONCAT_MAX_LEAVES + 20
        tree = {
            **{f"f{i}": jax.device_put(np.full((2,), i, np.float32)) for i in range(n)},
            **{f"i{i}": jax.device_put(np.full((3,), -i, np.int32)) for i in range(40)},
        }
        host = fetch_to_host(tree)
        for i in range(n):
            np.testing.assert_array_equal(host[f"f{i}"], np.full((2,), i, np.float32))
        for i in range(40):
            np.testing.assert_array_equal(host[f"i{i}"], np.full((3,), -i, np.int32))

    def test_leaves_are_independent_copies(self):
        """Slicing out of the coalesced buffer must copy — a view would
        pin the whole transfer buffer for the life of any one leaf."""
        from gordo_tpu.parallel.fleet import fetch_to_host

        host = fetch_to_host(self._tree(6))
        leaf = host["leaf_0"]
        assert leaf.base is None, "leaf is a view into the coalesced buffer"
