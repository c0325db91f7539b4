"""
Data providers: pluggable sources of raw per-tag time series.

Reference parity: gordo-core's ``GordoBaseDataProvider`` surface
(``load_series``, ``can_handle_tag``, ``to_dict``/``from_dict``) and
``RandomDataProvider``, the deterministic synthetic source used across the
reference's entire test suite (SURVEY.md §4).

Providers return host-side pandas Series; the dataset layer joins/resamples
them into aligned arrays which are then staged to TPU once per build — the
provider itself is deliberately device-unaware.
"""

import abc
import hashlib
import os
from typing import Dict, Iterable, List, Optional

import numpy as np
import pandas as pd

from ..utils.import_utils import import_location
from ..utils import capture_args
from .sensor_tag import SensorTag, normalize_sensor_tags


class GordoBaseDataProvider(abc.ABC):
    @abc.abstractmethod
    def load_series(
        self,
        train_start_date: pd.Timestamp,
        train_end_date: pd.Timestamp,
        tag_list: List[SensorTag],
        dry_run: bool = False,
        **kwargs,
    ) -> Iterable[pd.Series]:
        """Yield one raw ``pd.Series`` (DatetimeIndex) per requested tag."""

    @abc.abstractmethod
    def can_handle_tag(self, tag: SensorTag) -> bool:
        """Whether this provider can serve ``tag``."""

    def to_dict(self) -> dict:
        params = getattr(self, "_params", {})
        return {
            "type": f"{type(self).__module__}.{type(self).__name__}",
            **params,
        }

    @classmethod
    def from_dict(cls, config: dict) -> "GordoBaseDataProvider":
        config = dict(config)
        provider_type = config.pop("type", None)
        if provider_type is None:
            return cls(**config)
        if "." not in provider_type:
            # Bare names as the reference example configs use them
            # (examples/config.yaml: ``type: RandomDataProvider``); resolved
            # against this module, like gordo-core's provider registry.
            import sys

            candidate = getattr(sys.modules[__name__], provider_type, None)
            if candidate is None or not (
                isinstance(candidate, type) and issubclass(candidate, cls)
            ):
                raise ValueError(
                    f"Unknown data provider short name: {provider_type!r}"
                )
            ProviderClass: type = candidate
        else:
            ProviderClass = import_location(provider_type)
        return ProviderClass(**config)


class RandomDataProvider(GordoBaseDataProvider):
    """
    Deterministic synthetic sensor data for tests, examples and benchmarks.

    Each tag's series is a reproducible function of (tag name, date range,
    resolution): a smooth mixture of sinusoids plus noise, seeded by the tag
    name so the same config always yields the same data.
    """

    @capture_args
    def __init__(self, min_size: int = 100, max_size: int = 300, **kwargs):
        self.min_size = min_size
        self.max_size = max_size

    def can_handle_tag(self, tag: SensorTag) -> bool:
        return True

    def _rng_for(self, tag: SensorTag) -> np.random.RandomState:
        digest = hashlib.sha256(tag.name.encode()).digest()
        return np.random.RandomState(int.from_bytes(digest[:4], "little"))

    def load_series(
        self,
        train_start_date: pd.Timestamp,
        train_end_date: pd.Timestamp,
        tag_list: List[SensorTag],
        dry_run: bool = False,
        **kwargs,
    ) -> Iterable[pd.Series]:
        if train_start_date >= train_end_date:
            raise ValueError(
                f"train_start_date ({train_start_date}) must be before "
                f"train_end_date ({train_end_date})"
            )
        for tag in normalize_sensor_tags(tag_list):
            rng = self._rng_for(tag)
            n_points = rng.randint(self.min_size, self.max_size + 1)
            stamps = np.linspace(
                pd.Timestamp(train_start_date).value,
                pd.Timestamp(train_end_date).value,
                n_points,
            ).astype("int64")
            index = pd.DatetimeIndex(stamps.view("M8[ns]"))
            tz = getattr(train_start_date, "tz", None)
            if tz is not None:
                # .value above is UTC ns; localize back to the input tz
                index = index.tz_localize("UTC").tz_convert(tz)
            t = np.linspace(0.0, 2 * np.pi * rng.uniform(1.0, 6.0), n_points)
            base = rng.uniform(-50.0, 50.0)
            amplitude = rng.uniform(0.5, 10.0)
            values = (
                base
                + amplitude * np.sin(t + rng.uniform(0, 2 * np.pi))
                + 0.1 * amplitude * rng.standard_normal(n_points)
            )
            yield pd.Series(values, index=index, name=tag.name)


class FileDataProvider(GordoBaseDataProvider):
    """
    Tag series from parquet/CSV files on disk — the provider that makes
    ``local_build`` / ``build-fleet`` train on real exported data instead
    of synthetic series (reference surface: gordo-core's provider contract,
    SURVEY.md §2.9; resolvable from YAML as
    ``data_provider: {type: FileDataProvider, path: ...}``).

    Two on-disk layouts:

    - **wide file** — ``path`` is one file whose columns are tags and whose
      index (or ``timestamp_column``) holds timestamps::

          data_provider:
            type: FileDataProvider
            path: /data/plant-a.parquet
            timestamp_column: time       # optional; default: file index

    - **per-tag directory** — ``path`` is a directory of
      ``<tag-name>.parquet`` / ``<tag-name>.csv`` files, each holding one
      series (``timestamp_column`` + ``value_column``, defaulting to the
      first and second columns).

    ``tag_column_map`` renames: ``{config tag name: column or file name}``.
    Naive timestamps are localized to ``tz`` (default UTC) — gordo's train
    window bounds are always tz-aware.
    """

    _FORMATS = {
        ".parquet": "parquet",
        ".pq": "parquet",
        ".csv": "csv",
    }

    @capture_args
    def __init__(
        self,
        path: str,
        timestamp_column: Optional[str] = None,
        value_column: Optional[str] = None,
        tag_column_map: Optional[Dict[str, str]] = None,
        tz: str = "UTC",
        **kwargs,
    ):
        self.path = path
        self.timestamp_column = timestamp_column
        self.value_column = value_column
        self.tag_column_map = tag_column_map or {}
        self.tz = tz
        self._wide_frame: Optional[pd.DataFrame] = None

    # -- file plumbing -------------------------------------------------------

    def _format_of(self, path: str) -> str:
        ext = os.path.splitext(path)[1].lower()
        file_format = self._FORMATS.get(ext)
        if file_format is None:
            raise ValueError(
                f"Unsupported file format {ext!r} for {path!r} "
                f"(supported: {sorted(self._FORMATS)})"
            )
        return file_format

    def _read_frame(self, path: str) -> pd.DataFrame:
        if self._format_of(path) == "parquet":
            frame = pd.read_parquet(path)
        else:
            frame = pd.read_csv(path)
        ts_col = self.timestamp_column
        if ts_col is None and not isinstance(frame.index, pd.DatetimeIndex):
            ts_col = frame.columns[0]
        if ts_col is not None:
            if ts_col not in frame.columns:
                raise ValueError(
                    f"Timestamp column {ts_col!r} not present in {path!r} "
                    f"(columns: {list(frame.columns)})"
                )
            frame = frame.set_index(ts_col)
        frame.index = pd.DatetimeIndex(pd.to_datetime(frame.index))
        if frame.index.tz is None:
            frame.index = frame.index.tz_localize(self.tz)
        return frame.sort_index()

    def _column_for(self, tag: SensorTag) -> str:
        return self.tag_column_map.get(tag.name, tag.name)

    def _is_directory_layout(self) -> bool:
        return os.path.isdir(self.path)

    def _tag_file(self, tag: SensorTag) -> Optional[str]:
        column = self._column_for(tag)
        for ext in self._FORMATS:
            candidate = os.path.join(self.path, column + ext)
            if os.path.isfile(candidate):
                return candidate
        return None

    def _wide(self) -> pd.DataFrame:
        if self._wide_frame is None:
            self._wide_frame = self._read_frame(self.path)
        return self._wide_frame

    # -- provider contract ---------------------------------------------------

    def can_handle_tag(self, tag: SensorTag) -> bool:
        if self._is_directory_layout():
            return self._tag_file(tag) is not None
        try:
            return self._column_for(tag) in self._wide().columns
        except (OSError, ValueError):
            return False

    def _series_for(self, tag: SensorTag) -> pd.Series:
        if self._is_directory_layout():
            tag_file = self._tag_file(tag)
            if tag_file is None:
                raise ValueError(
                    f"No file for tag {tag.name!r} under {self.path!r}"
                )
            frame = self._read_frame(tag_file)
            column = self.value_column or frame.columns[0]
            if column not in frame.columns:
                raise ValueError(
                    f"Value column {column!r} not present in {tag_file!r}"
                )
            return frame[column].rename(tag.name)
        frame = self._wide()
        column = self._column_for(tag)
        if column not in frame.columns:
            raise ValueError(
                f"Tag {tag.name!r} (column {column!r}) not present in "
                f"{self.path!r} (columns: {list(frame.columns)})"
            )
        return frame[column].rename(tag.name)

    def load_series(
        self,
        train_start_date: pd.Timestamp,
        train_end_date: pd.Timestamp,
        tag_list: List[SensorTag],
        dry_run: bool = False,
        **kwargs,
    ) -> Iterable[pd.Series]:
        if train_start_date >= train_end_date:
            raise ValueError(
                f"train_start_date ({train_start_date}) must be before "
                f"train_end_date ({train_end_date})"
            )
        for tag in normalize_sensor_tags(tag_list):
            series = self._series_for(tag)
            yield series[
                (series.index >= train_start_date) & (series.index < train_end_date)
            ]


class ListBackedDataProvider(GordoBaseDataProvider):
    """In-memory provider wrapping pre-built series; used by tests/tools."""

    @capture_args
    def __init__(self, series: Optional[List[pd.Series]] = None, **kwargs):
        self.series = series or []

    def can_handle_tag(self, tag: SensorTag) -> bool:
        return any(s.name == tag.name for s in self.series)

    def load_series(
        self,
        train_start_date: pd.Timestamp,
        train_end_date: pd.Timestamp,
        tag_list: List[SensorTag],
        dry_run: bool = False,
        **kwargs,
    ) -> Iterable[pd.Series]:
        by_name = {s.name: s for s in self.series}
        for tag in normalize_sensor_tags(tag_list):
            series = by_name[tag.name]
            yield series[(series.index >= train_start_date) & (series.index < train_end_date)]


class InfluxDataProvider(GordoBaseDataProvider):
    """
    Tag series from an InfluxDB (1.x line) time-series database — the
    production reader that closes the data loop the Influx *forwarder*
    opens (client/forwarders.py ForwardPredictionsIntoInflux; the
    reference ecosystem reads sensor data through gordo-core's influx
    provider, pinned at
    /root/reference/requirements/full_requirements.txt:139-142, and its
    Argo client step replays predictions into the same Influx the
    dashboards read — argo-workflow.yml.template:1374-1376).

    Two on-wire layouts:

    - **sensor layout** (default): one shared ``measurement`` whose rows
      are distinguished by an Influx tag (``tag_key``, default ``tag``)
      holding the sensor name, values in field ``value_name``::

          data_provider:
            type: InfluxDataProvider
            measurement: sensors
            uri: user:pass@influx:8086/dbname

    - **field layout** (``fields_are_tags: true``): sensor names are the
      measurement's *fields* — exactly what
      ``ForwardPredictionsIntoInflux`` writes (pipe-joined prediction
      columns as fields, one ``machine`` Influx tag), so a dataset can
      train on replayed predictions::

          data_provider:
            type: InfluxDataProvider
            measurement: predictions
            fields_are_tags: true
            where_tags: {machine: my-machine}

    ``client`` injects a ready ``influxdb.DataFrameClient``-compatible
    object (tests use an in-memory fake); otherwise ``uri`` is parsed
    exactly like the forwarder's
    (``<username>:<password>@<host>:<port>/<db_name>``).
    """

    @capture_args
    def __init__(
        self,
        measurement: str,
        value_name: str = "Value",
        tag_key: str = "tag",
        fields_are_tags: bool = False,
        where_tags: Optional[Dict[str, str]] = None,
        uri: Optional[str] = None,
        api_key: Optional[str] = None,
        api_key_header: str = "Ocp-Apim-Subscription-Key",
        client=None,
        **kwargs,
    ):
        self.measurement = measurement
        self.value_name = value_name
        self.tag_key = tag_key
        self.fields_are_tags = fields_are_tags
        self.where_tags = where_tags or {}
        self.uri = uri
        self.api_key = api_key
        self.api_key_header = api_key_header
        self.influx_client = client
        if self.influx_client is None and uri:
            self.influx_client = self._client_from_uri(uri)

    def _client_from_uri(self, uri: str):  # pragma: no cover - needs influxdb
        try:
            from influxdb import DataFrameClient
        except ImportError as exc:
            raise ImportError(
                "The influxdb package is required for InfluxDataProvider "
                "(or pass client=...)"
            ) from exc

        username, password, host, port, *_, db_name = (
            uri.replace("/", ":").replace("@", ":").split(":")
        )
        return DataFrameClient(
            host=host,
            port=int(port),
            username=username,
            password=password,
            database=db_name,
            headers={self.api_key_header: self.api_key} if self.api_key else None,
        )

    def _require_client(self):
        if self.influx_client is None:
            raise ValueError(
                "InfluxDataProvider has no client; pass uri=... or client=..."
            )
        return self.influx_client

    @staticmethod
    def _escape(identifier: str) -> str:
        # InfluxQL string literals backslash-escape; backslashes first so
        # a trailing backslash can't swallow the closing quote (or a
        # crafted value extend the WHERE clause)
        return identifier.replace("\\", "\\\\").replace("'", "\\'")

    def _query_series(
        self,
        tag: SensorTag,
        train_start_date: pd.Timestamp,
        train_end_date: pd.Timestamp,
    ) -> pd.Series:
        client = self._require_client()
        start_ns = int(pd.Timestamp(train_start_date).value)
        end_ns = int(pd.Timestamp(train_end_date).value)
        conditions = [f"time >= {start_ns} AND time < {end_ns}"]
        if self.fields_are_tags:
            field = tag.name
        else:
            field = self.value_name
            conditions.append(
                f"\"{self.tag_key}\" = '{self._escape(tag.name)}'"
            )
        for key, value in self.where_tags.items():
            conditions.append(f"\"{key}\" = '{self._escape(str(value))}'")
        query = (
            f'SELECT "{field}" FROM "{self.measurement}" '
            f"WHERE {' AND '.join(conditions)}"
        )
        result = client.query(query)
        frame = result.get(self.measurement) if hasattr(result, "get") else None
        if frame is None or len(frame) == 0:
            raise ValueError(
                f"No data for tag {tag.name!r} in measurement "
                f"{self.measurement!r} over [{train_start_date}, "
                f"{train_end_date})"
            )
        series = frame[field].rename(tag.name)
        index = pd.DatetimeIndex(pd.to_datetime(series.index))
        if index.tz is None:
            index = index.tz_localize("UTC")
        series.index = index
        return series.sort_index()

    def can_handle_tag(self, tag: SensorTag) -> bool:
        # Availability is a per-window property in a TSDB; existence is
        # checked by the read itself (ValueError names the tag/window).
        return self.influx_client is not None or bool(self.uri)

    def load_series(
        self,
        train_start_date: pd.Timestamp,
        train_end_date: pd.Timestamp,
        tag_list: List[SensorTag],
        dry_run: bool = False,
        **kwargs,
    ) -> Iterable[pd.Series]:
        if train_start_date >= train_end_date:
            raise ValueError(
                f"train_start_date ({train_start_date}) must be before "
                f"train_end_date ({train_end_date})"
            )
        for tag in normalize_sensor_tags(tag_list):
            yield self._query_series(tag, train_start_date, train_end_date)
