"""What a dataset says about its own fetch (PR 24): the seconds of the
last ``get_data`` by part, which the fleet builder puts on the
machine's ``machine_fetch`` span."""

import pytest

from gordo_tpu.dataset import GordoBaseDataset
from gordo_tpu.dataset.exceptions import InsufficientDataError

CONFIG = {
    "type": "RandomDataset",
    "train_start_date": "2020-01-01T00:00:00+00:00",
    "train_end_date": "2020-01-03T00:00:00+00:00",
    "tag_list": ["t1", "t2", "t3"],
}


def test_fetch_seconds_name_the_three_parts_of_the_last_fetch():
    dataset = GordoBaseDataset.from_dict(CONFIG)
    assert dataset.fetch_seconds == {}
    X, _ = dataset.get_data()
    first = dict(dataset.fetch_seconds)
    assert set(first) == {"provider_read", "resample_join", "row_filter"}
    assert all(seconds >= 0.0 for seconds in first.values())
    assert first["provider_read"] > 0 and first["resample_join"] > 0
    # of the last fetch, not a running total
    dataset.get_data()
    assert dataset.fetch_seconds["resample_join"] < first["resample_join"] * 20
    assert set(dataset.fetch_seconds) == set(first)
    assert len(X) > 0


def test_fetch_cpu_seconds_has_the_three_parts_on_the_threads_own_clock():
    """Beside each part's wall seconds the CPU seconds of the thread
    that ran it (``time.thread_time()``): what the part computed, free
    of its waits; of the last fetch, like the wall seconds."""
    dataset = GordoBaseDataset.from_dict(CONFIG)
    dataset.get_data()
    # a fetch nobody records reads one clock (the fleet builder sets the
    # flag where it writes spans)
    assert dataset.fetch_cpu_seconds == {} and dataset.fetch_cpu_timed is False
    dataset.fetch_cpu_timed = True
    dataset.get_data()
    cpu, wall = dict(dataset.fetch_cpu_seconds), dict(dataset.fetch_seconds)
    assert set(cpu) == set(wall) == {"provider_read", "resample_join", "row_filter"}
    for part in cpu:
        assert 0.0 <= cpu[part] <= wall[part] + 0.025  # two ticks of a coarse CPU clock
    assert cpu["resample_join"] > 0
    dataset.get_data()
    assert set(dataset.fetch_cpu_seconds) == set(cpu)
    assert dataset.fetch_cpu_seconds["resample_join"] < cpu["resample_join"] * 20


def test_a_fetch_that_fails_keeps_what_it_measured_so_far():
    dataset = GordoBaseDataset.from_dict({**CONFIG, "n_samples_threshold": 10**9})
    dataset.fetch_cpu_timed = True
    with pytest.raises(InsufficientDataError):
        dataset.get_data()
    assert set(dataset.fetch_seconds) == {"provider_read", "resample_join", "row_filter"}
    assert set(dataset.fetch_cpu_seconds) == set(dataset.fetch_seconds)


def test_the_timing_is_not_part_of_the_datasets_definition():
    dataset = GordoBaseDataset.from_dict(CONFIG)
    before = dataset.to_dict()
    dataset.get_data()
    assert dataset.to_dict() == before
    assert "fetch_seconds" not in dataset.get_metadata()
    assert "fetch_cpu_seconds" not in dataset.get_metadata()
