"""
Dataset layer: config-described time-series datasets yielding (X, y) frames.

Reference parity: gordo-core's ``GordoBaseDataset`` surface as consumed by
gordo (SURVEY.md §2.9): ``from_dict`` / ``to_dict`` / ``get_data`` /
``get_metadata``, ``TimeSeriesDataset`` (join + resample + filter of per-tag
series) and ``RandomDataset`` (synthetic provider variant used in every test
and example config).

TPU-first note: ``get_data`` returns host pandas frames (the provider/IO
plane), while ``trainable_arrays`` hands back float32 numpy ready for a
single ``jax.device_put`` — the fleet builder stages one stacked array per
compilation bucket instead of thousands of small transfers.
"""

import abc
import contextlib
import logging
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as np
import pandas as pd

from ..utils.import_utils import import_location
from ..utils import capture_args
from ..utils.profiling import annotate
from .data_provider import GordoBaseDataProvider, RandomDataProvider
from .exceptions import ConfigException, InsufficientDataError
from .sensor_tag import (
    SensorTag,
    normalize_sensor_tags,
    to_list_of_strings,
    unique_tag_names,
)

logger = logging.getLogger(__name__)

DEFAULT_RESOLUTION = "10min"

#: aggregations where an all-NaN bin stays NaN — the precondition for the
#: one-pass resample fast path's span-intersection trim ("sum"/"count"
#: would turn out-of-span bins into 0 and fabricate rows)
_NAN_PRESERVING_AGGS = frozenset(
    {"mean", "median", "min", "max", "first", "last", "std", "var"}
)


def _interpolate_linear_limited(data: pd.DataFrame, limit: int) -> pd.DataFrame:
    """
    ``DataFrame.interpolate(method="linear", limit=limit)`` in vectorized
    numpy — bit-identical to pandas (parity-tested against it in
    tests/dataset/test_datasets.py) but ~100× cheaper: pandas routes the
    limit logic through ``apply_along_axis`` per column, which measured
    ~0.25s per machine on the build path (minutes at 1000-machine scale).

    Pandas "linear" semantics (positional, ignores index spacing):
    leading NaNs stay NaN; interior gaps fill linearly between anchors but
    only the first ``limit`` positions of each gap; trailing NaNs repeat
    the last valid value, also up to ``limit``.
    """
    try:
        values = data.to_numpy(dtype=np.float64, copy=True)
    except (TypeError, ValueError):
        # non-numeric columns (never produced by resample, but a custom
        # provider could) — keep pandas' own path for them
        return data.interpolate(method="linear", limit=limit)
    n = len(values)
    if n == 0:
        return data
    positions = np.arange(n)
    for col in range(values.shape[1]):
        column = values[:, col]
        nan_mask = np.isnan(column)
        if not nan_mask.any():
            continue
        valid = ~nan_mask
        if not valid.any():
            continue
        valid_idx = np.flatnonzero(valid)
        filled = np.interp(positions, valid_idx, column[valid_idx])
        # distance to the previous valid observation gates the fill
        prev_valid = np.maximum.accumulate(np.where(valid, positions, -1))
        gap_run = positions - prev_valid
        fill = nan_mask & (prev_valid >= 0) & (gap_run <= limit)
        column[fill] = filled[fill]
    result = pd.DataFrame(values, index=data.index, columns=data.columns)
    # pandas.interpolate preserves per-column dtypes; the f64 work buffer
    # must not leak into the result for e.g. float32 input frames, or the
    # drop-in-replacement claim only holds for f64 callers. (Duplicate
    # column labels keep the f64 frame — astype-by-dict can't address
    # them, and the resample product path never produces duplicates.)
    if data.columns.is_unique and any(dt != np.float64 for dt in data.dtypes):
        result = result.astype(dict(zip(data.columns, data.dtypes)))
    return result


def normalize_frequency(resolution: str) -> str:
    """
    Accept legacy pandas offset aliases ('10T', '1H') alongside the modern
    spellings pandas ≥3 requires ('10min', '1h').

    >>> normalize_frequency("10T")
    '10min'
    >>> normalize_frequency("1H")
    '1h'
    >>> normalize_frequency("30s")
    '30s'
    """
    replacements = {"T": "min", "H": "h", "S": "s", "L": "ms"}
    for legacy, modern in replacements.items():
        if resolution.endswith(legacy):
            return resolution[: -len(legacy)] + modern
    return resolution


class GordoBaseDataset(abc.ABC):
    @abc.abstractmethod
    def get_data(self) -> Tuple[pd.DataFrame, pd.DataFrame]:
        """Return (X, y) training frames with aligned DatetimeIndex."""

    @abc.abstractmethod
    def get_metadata(self) -> dict:
        """Dataset build metadata recorded by the builder."""

    def to_dict(self) -> dict:
        params = dict(getattr(self, "_params", {}))
        if "data_provider" in params and isinstance(
            params["data_provider"], GordoBaseDataProvider
        ):
            params["data_provider"] = params["data_provider"].to_dict()
        params["tag_list"] = [
            tag.to_json() if isinstance(tag, SensorTag) else tag
            for tag in params.get("tag_list", [])
        ]
        if params.get("target_tag_list"):
            params["target_tag_list"] = [
                tag.to_json() if isinstance(tag, SensorTag) else tag
                for tag in params["target_tag_list"]
            ]
        for key in ("train_start_date", "train_end_date"):
            if key in params and isinstance(params[key], pd.Timestamp):
                params[key] = params[key].isoformat()
        params["type"] = f"{type(self).__module__}.{type(self).__name__}"
        return params

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "GordoBaseDataset":
        """
        Resolve ``config["type"]`` (default ``TimeSeriesDataset``) and
        construct the dataset; mirrors gordo-core's dataset factory consumed
        at gordo/machine/machine.py and builder/build_model.py.
        """
        config = dict(config)
        # gordo-core accepts `tags` / `target_tags` aliases (the reference's
        # examples/config.yaml uses `tags:`); normalize to the canonical keys.
        for alias, canonical in (("tags", "tag_list"), ("target_tags", "target_tag_list")):
            if alias in config and canonical not in config:
                config[canonical] = config.pop(alias)
        dataset_type = config.pop("type", None)
        if dataset_type is None or dataset_type in (
            "TimeSeriesDataset",
            "gordo_dataset.datasets.TimeSeriesDataset",
        ):
            DatasetClass: type = TimeSeriesDataset
        elif dataset_type in ("RandomDataset", "gordo_dataset.datasets.RandomDataset"):
            DatasetClass = RandomDataset
        else:
            DatasetClass = import_location(dataset_type)
        return DatasetClass(**config)


def _parse_timestamp(value: Union[str, pd.Timestamp]) -> pd.Timestamp:
    ts = pd.Timestamp(value) if not isinstance(value, pd.Timestamp) else value
    if ts.tz is None:
        raise ConfigException(
            f"Timestamp {value!r} must be timezone-aware (reference requires "
            "tz-aware datetimes: gordo/machine/validators.py:234-253)"
        )
    return ts


class TimeSeriesDataset(GordoBaseDataset):
    """
    Joins per-tag series from a data provider onto a uniform time grid.

    Steps in ``get_data``: load raw series → resample each to ``resolution``
    with ``aggregation_methods`` → inner-join across tags → apply
    ``row_filter`` / ``known_filter_periods`` → enforce
    ``n_samples_threshold`` → split into X (tag_list) and y
    (target_tag_list, defaulting to tag_list).
    """

    @capture_args
    def __init__(
        self,
        train_start_date: Union[str, pd.Timestamp],
        train_end_date: Union[str, pd.Timestamp],
        tag_list: List[Union[str, dict, SensorTag]],
        target_tag_list: Optional[List[Union[str, dict, SensorTag]]] = None,
        data_provider: Optional[Union[dict, GordoBaseDataProvider]] = None,
        resolution: str = DEFAULT_RESOLUTION,
        row_filter: str = "",
        known_filter_periods: Optional[List[Tuple[str, str]]] = None,
        aggregation_methods: Union[str, List[str]] = "mean",
        n_samples_threshold: int = 0,
        low_threshold: Optional[float] = None,
        high_threshold: Optional[float] = None,
        interpolation_method: str = "linear_interpolation",
        interpolation_limit: str = "8h",
        asset: Optional[str] = None,
        **kwargs,
    ):
        self.train_start_date = _parse_timestamp(train_start_date)
        self.train_end_date = _parse_timestamp(train_end_date)
        if self.train_start_date >= self.train_end_date:
            raise ConfigException(
                f"train_end_date ({self.train_end_date}) must be after "
                f"train_start_date ({self.train_start_date})"
            )
        self.tag_list = normalize_sensor_tags(tag_list, asset=asset)
        self.target_tag_list = (
            normalize_sensor_tags(target_tag_list, asset=asset)
            if target_tag_list
            else list(self.tag_list)
        )
        unique_tag_names(self.tag_list)
        if data_provider is None:
            data_provider = RandomDataProvider()
        self.data_provider = (
            GordoBaseDataProvider.from_dict(data_provider)
            if isinstance(data_provider, dict)
            else data_provider
        )
        self.resolution = normalize_frequency(resolution)
        self.row_filter = row_filter
        self.known_filter_periods = known_filter_periods or []
        self.aggregation_methods = aggregation_methods
        self.n_samples_threshold = n_samples_threshold
        self.low_threshold = low_threshold
        self.high_threshold = high_threshold
        self.interpolation_method = interpolation_method
        self.interpolation_limit = interpolation_limit
        self._metadata: Dict[str, Any] = {}
        #: seconds of the last ``get_data`` by part (``provider_read``,
        #: ``resample_join``, ``row_filter``): the fleet builder puts them
        #: on the machine's ``machine_fetch`` span
        self.fetch_seconds: Dict[str, float] = {}
        #: the same parts on the calling thread's CPU clock
        #: (``time.thread_time()``): what the fetch computed, free of
        #: its waits for the source, for the GIL, for a core. Kept only
        #: where ``fetch_cpu_timed`` is set (the fleet builder sets it
        #: where it records spans): a fetch nobody records reads one clock
        self.fetch_cpu_seconds: Dict[str, float] = {}
        self.fetch_cpu_timed = False

    def _load_and_join(self) -> pd.DataFrame:
        all_tags = unique_tag_names(list(self.tag_list) + list(self.target_tag_list))
        # the provider's share of a fetch (an artefact of the data source:
        # a random provider generates, a lake provider waits on I/O)
        # apart from the resample/join every source pays
        with self._timed("provider_read"):
            series_list = list(
                self.data_provider.load_series(
                    self.train_start_date,
                    self.train_end_date,
                    list(all_tags.values()),
                )
            )
        with self._timed("resample_join"):
            return self._join(series_list)

    @contextlib.contextmanager
    def _timed(self, part: str):
        """Add the enclosed block's seconds to ``fetch_seconds[part]``
        and, where ``fetch_cpu_timed``, the calling thread's CPU seconds
        to ``fetch_cpu_seconds[part]`` (and name it in a profiler
        session's host plane)."""
        started = time.perf_counter()
        cpu_started = time.thread_time() if self.fetch_cpu_timed else None
        try:
            with annotate(f"dataset:{part}"):
                yield
        finally:
            if cpu_started is not None:
                self.fetch_cpu_seconds[part] = (
                    self.fetch_cpu_seconds.get(part, 0.0)
                    + time.thread_time()
                    - cpu_started
                )
            self.fetch_seconds[part] = (
                self.fetch_seconds.get(part, 0.0) + time.perf_counter() - started
            )

    def _join(self, series_list: List[pd.Series]) -> pd.DataFrame:
        if not series_list:
            raise InsufficientDataError("Data provider returned no series")

        for series in series_list:
            if series.empty:
                raise InsufficientDataError(
                    f"Tag {series.name!r} has no data in "
                    f"[{self.train_start_date}, {self.train_end_date}]"
                )

        data = None
        if (
            isinstance(self.aggregation_methods, str)
            and self.aggregation_methods in _NAN_PRESERVING_AGGS
        ):
            seconds = pd.Timedelta(self.resolution).total_seconds()
            # one resample pass over an aligned frame is ~n_tags× faster
            # than per-series resampling, and bin-exact only when the
            # resolution divides a day (bins midnight-anchored for every
            # series regardless of its first observation's day)
            if seconds > 0 and 86400 % seconds == 0:
                try:
                    data = self._resample_joined(series_list)
                except (ValueError, TypeError, pd.errors.InvalidIndexError):
                    data = None  # ragged/duplicate indexes: per-series path
        if data is None:
            resampled = []
            for series in series_list:
                agg = series.resample(self.resolution).agg(self.aggregation_methods)
                if isinstance(agg, pd.DataFrame):  # multiple aggregation methods
                    agg.columns = [f"{series.name}_{m}" for m in agg.columns]
                resampled.append(agg)
            data = pd.concat(resampled, axis=1, join="inner")
            if isinstance(self.aggregation_methods, str):
                data.columns = [s.name for s in series_list]
        interp_limit = max(
            int(pd.Timedelta(self.interpolation_limit) / pd.Timedelta(self.resolution)),
            1,
        )
        if self.interpolation_method == "linear_interpolation":
            data = _interpolate_linear_limited(data, interp_limit)
        elif self.interpolation_method == "ffill":
            data = data.ffill(limit=interp_limit)
        return data.dropna()

    def _resample_joined(self, series_list: List[pd.Series]) -> pd.DataFrame:
        """
        Single-aggregation fast path: every tag resampled in ONE pass
        (only for the NaN-preserving aggregations in
        ``_NAN_PRESERVING_AGGS`` — a method like ``sum`` turns the all-NaN
        bins outside a tag's span into 0, which would defeat the
        span-intersection trim below and fabricate data).

        Equivalent to per-series resample + inner concat: the raw series
        are outer-aligned (NaN where a tag lacks a stamp; the NaN-skipping
        per-column agg then sees exactly each tag's own observations per
        bin), resampled as one frame, and trimmed to the intersection of
        per-tag spans — a tag's first/last valid bins are the bins holding
        its first/last observations, exactly where its own resample would
        start and end. Raises for ragged/duplicate indexes the aligner
        can't handle; the caller falls back to the per-series path.

        The outer alignment itself is a numpy int64-ns union +
        searchsorted scatter — ``pd.concat(axis=1, sort=True)`` does a
        k-way index union through per-series reindex machinery that
        measured ~20ms per machine on the build path (20 tags).
        """
        raw = self._outer_align(series_list)
        data = raw.resample(self.resolution).agg(self.aggregation_methods)
        # Trim by bin LABELS of each series' observed span (floor is
        # midnight-anchored like resample's origin for day-dividing
        # resolutions) — not by first/last valid aggregated values: a
        # boundary bin can legitimately aggregate to NaN (std of a single
        # observation, NaN-valued raw samples) and must still be kept,
        # exactly as the per-series inner join keeps it.
        start = max(s.index.min().floor(self.resolution) for s in series_list)
        end = min(s.index.max().floor(self.resolution) for s in series_list)
        return data.loc[start:end]

    @staticmethod
    def _outer_align(series_list: List[pd.Series]) -> pd.DataFrame:
        """NaN-padded outer join of the raw tag series, equivalent to
        ``pd.concat(series_list, axis=1, sort=True)`` for unique sorted
        tz-homogeneous indexes; raises InvalidIndexError otherwise (the
        resample-path caller falls back to per-series resampling, exactly
        as it does when pandas' own concat raises)."""
        def index_unit(index) -> str:
            dtype = index.dtype
            if hasattr(dtype, "unit"):  # tz-aware DatetimeTZDtype
                return dtype.unit
            return np.datetime_data(dtype)[0]

        tzs = {getattr(s.index, "tz", None) for s in series_list}
        int_indexes = []
        units = set()
        for s in series_list:
            if not isinstance(s.index, pd.DatetimeIndex) or not s.index.is_unique:
                raise pd.errors.InvalidIndexError(f"index of {s.name!r}")
            units.add(index_unit(s.index))
            int_indexes.append(s.index.asi8)
        # asi8 is in the index's own resolution (pandas ≥2 indexes can be
        # s/ms/us/ns), so the epoch ints only union across a single unit
        if len(tzs) > 1 or len(units) > 1:
            raise pd.errors.InvalidIndexError("mixed index timezones or units")
        unit = units.pop()
        union = np.unique(np.concatenate(int_indexes))
        values = np.full((len(union), len(series_list)), np.nan)
        for j, s in enumerate(series_list):
            values[np.searchsorted(union, int_indexes[j]), j] = s.to_numpy(
                dtype=np.float64, na_value=np.nan
            )
        index = pd.DatetimeIndex(union.view(f"M8[{unit}]"))
        tz = tzs.pop()
        if tz is not None:
            index = index.tz_localize("UTC").tz_convert(tz)
        return pd.DataFrame(
            values, index=index, columns=[s.name for s in series_list]
        )

    def _apply_filters(self, data: pd.DataFrame) -> pd.DataFrame:
        n_before = len(data)
        for period in self.known_filter_periods:
            if not period:
                continue
            start, end = pd.Timestamp(period[0]), pd.Timestamp(period[1])
            data = data[(data.index < start) | (data.index > end)]
        if self.row_filter:
            data = data.query(self.row_filter)
        if self.low_threshold is not None:
            data = data[(data > self.low_threshold).all(axis=1)]
        if self.high_threshold is not None:
            data = data[(data < self.high_threshold).all(axis=1)]
        self._metadata["filtered_rows"] = n_before - len(data)
        return data

    def get_data(self) -> Tuple[pd.DataFrame, pd.DataFrame]:
        self.fetch_seconds, self.fetch_cpu_seconds = {}, {}
        data = self._load_and_join()
        with self._timed("row_filter"):
            data = self._apply_filters(data)
        if len(data) <= self.n_samples_threshold:
            raise InsufficientDataError(
                f"Dataset resolved to {len(data)} rows, below threshold "
                f"{self.n_samples_threshold}"
            )
        x_names = to_list_of_strings(self.tag_list)
        y_names = to_list_of_strings(self.target_tag_list)
        if not isinstance(self.aggregation_methods, str):
            # Multiple aggregations widen each tag into '{tag}_{method}'
            x_names = [
                f"{name}_{method}"
                for name in x_names
                for method in self.aggregation_methods
            ]
            y_names = [
                f"{name}_{method}"
                for name in y_names
                for method in self.aggregation_methods
            ]
        X = data[x_names]
        y = data[y_names]
        self._metadata.update(
            {
                "train_start_date": self.train_start_date.isoformat(),
                "train_end_date": self.train_end_date.isoformat(),
                "resolution": self.resolution,
                "row_count": len(X),
                "tag_list": [t.to_json() for t in self.tag_list],
                "target_tag_list": [t.to_json() for t in self.target_tag_list],
                "x_hist": self._column_histograms(X),
            }
        )
        return X, y

    @staticmethod
    def _column_histograms(X: pd.DataFrame) -> Dict[str, Dict[str, float]]:
        """Per-tag summary stats in four vectorized reductions (pandas'
        per-column Series reductions measured ~10ms/machine at 20 tags).
        ``ddof=1`` matches ``Series.std``; NaN-aware to keep parity on
        frames that skipped interpolation."""
        values = X.to_numpy(dtype=np.float64)
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)  # all-NaN columns
            mins = np.nanmin(values, axis=0)
            maxs = np.nanmax(values, axis=0)
            means = np.nanmean(values, axis=0)
            stds = np.nanstd(values, axis=0, ddof=1)
        return {
            str(name): {
                "min": float(mins[i]),
                "max": float(maxs[i]),
                "mean": float(means[i]),
                "std": float(stds[i]),
            }
            for i, name in enumerate(X.columns)
        }

    def trainable_arrays(self) -> Tuple[np.ndarray, np.ndarray, pd.Index]:
        """(X, y) as float32 numpy plus the shared index — one device_put away
        from TPU."""
        X, y = self.get_data()
        return (
            np.ascontiguousarray(X.to_numpy(), dtype=np.float32),
            np.ascontiguousarray(y.to_numpy(), dtype=np.float32),
            X.index,
        )

    def get_metadata(self) -> dict:
        return dict(self._metadata)


class RandomDataset(TimeSeriesDataset):
    """TimeSeriesDataset pinned to the deterministic RandomDataProvider."""

    @capture_args
    def __init__(
        self,
        train_start_date: Union[str, pd.Timestamp],
        train_end_date: Union[str, pd.Timestamp],
        tag_list: List[Union[str, dict, SensorTag]],
        **kwargs,
    ):
        kwargs.pop("data_provider", None)
        super().__init__(
            train_start_date=train_start_date,
            train_end_date=train_end_date,
            tag_list=tag_list,
            data_provider=RandomDataProvider(),
            **kwargs,
        )
