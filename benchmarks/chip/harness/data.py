"""
Everything a run draws from ``--seed``: the machines documents that
``build-fleet`` is given, and the request rows a served machine is asked
to score.

A machine's training data is a function of its tag names
(``RandomDataProvider`` seeds each tag's series by the tag's name), so
new names are new data: the same seed and job index give the same
machines, every other pair gives others.
"""

import datetime
from typing import Any, Dict, List

PROJECT = "chipbench"
TRAIN_START = datetime.datetime(2020, 1, 1, tzinfo=datetime.timezone.utc)
ROWS_PER_DAY = 144  # 10-minute resolution


def machine_names(seed: int, job: int, machines: int) -> List[str]:
    """Names as kubernetes wants them: lower case, digits and ``-``."""
    return [f"s{seed}-j{job}-m{i:04d}" for i in range(machines)]


def history_rows(days: int) -> int:
    return days * ROWS_PER_DAY + 1


def machines_document(
    config: Dict[str, Any], seed: int, job: int, machines: int, history_days: int
) -> Dict[str, Any]:
    """The ``build-fleet`` input of one job: ``machines`` machines of
    the configuration's model, each with ``history_days`` of 10-minute
    rows under tag names of its own."""
    end = TRAIN_START + datetime.timedelta(days=history_days)
    rows = history_rows(history_days)
    model = {
        "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
            "base_estimator": {
                "sklearn.pipeline.Pipeline": {
                    "steps": [
                        "sklearn.preprocessing.MinMaxScaler",
                        config["estimator"],
                    ]
                }
            }
        }
    }
    return {
        "project_name": PROJECT,
        "machines": [
            {
                "name": name,
                "model": model,
                "dataset": {
                    "type": "TimeSeriesDataset",
                    "data_provider": {
                        "type": "RandomDataProvider",
                        "min_size": rows,
                        "max_size": rows,
                    },
                    "train_start_date": TRAIN_START.isoformat(),
                    "train_end_date": end.isoformat(),
                    "resolution": config["resolution"],
                    "tag_list": [
                        f"{name}-t{j:02d}" for j in range(config["tags"])
                    ],
                },
            }
            for name in machine_names(seed, job, machines)
        ],
    }


def request_rows(tag_ranges: Dict[str, Dict[str, float]], rows: int, rng):
    """``rows`` rows inside each tag's training range, as a DataFrame on
    a 10-minute index after the training period. ``tag_ranges`` is the
    artifact's ``x_hist`` (tag -> min, max); ``rng`` a
    ``numpy.random.RandomState``."""
    import pandas as pd

    return pd.DataFrame(
        {
            tag: rng.uniform(hist["min"], hist["max"], rows)
            for tag, hist in tag_ranges.items()
        },
        index=pd.date_range("2021-01-01", periods=rows, freq="10min", tz="UTC"),
    )
