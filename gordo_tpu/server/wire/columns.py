"""
The columnar response model the wire fast path assembles into.

A :class:`WireTable` is the serving pipeline's in-flight response shape:
an ordered list of ``(group, sub, values)`` columns over one shared index
— exactly the structure every wire encoder needs (the nested JSON dict's
``{group: {sub: {key: value}}}``, an Arrow record batch's fields, a
parquet/pandas MultiIndex frame) without committing to any of them. The
point of the type is what it is NOT: a pandas DataFrame. The legacy
response path built a MultiIndex frame column-group by column-group
(``make_base_dataframe`` + joins) and then walked it cell by cell into
wire dicts — measured at ~70% of full-route p50 (a CPU run, PR 7:
``response_assemble`` 493ms of 686ms). Here every column is composed
once, as a numpy array, and handed to the encoder as-is.
"""

from typing import Any, Iterator, List, NamedTuple, Optional, Tuple

import numpy as np
import pandas as pd


class WireColumn(NamedTuple):
    """One response column: ``group`` is the top-level wire key
    (``model-output``, ``tag-anomaly-scaled``, ...), ``sub`` the tag-level
    key ('' for scalar groups like ``total-anomaly-scaled``), ``values``
    a 1-D numpy array or a plain list (object columns: ISO strings /
    None)."""

    group: str
    sub: str
    values: Any


class WireTable:
    """An ordered columnar response over one index.

    ``index`` is the (already output-aligned) pandas index; ``keys`` are
    the wire keys the JSON encoders need — the same strings
    ``server.utils.index_wire_keys`` produces, computed once per table
    (lazily: the Arrow encoder never needs them).
    """

    __slots__ = ("index", "columns", "_keys")

    def __init__(self, index: pd.Index, columns: List[WireColumn]):
        self.index = index
        self.columns = columns
        self._keys: Optional[list] = None

    @property
    def keys(self) -> list:
        if self._keys is None:
            from .. import utils as server_utils

            if isinstance(self.index, pd.DatetimeIndex):
                self._keys = server_utils.index_wire_keys(self.index)
            else:
                # non-datetime indexes keep their native values — the
                # JSON layer coerces them to string keys exactly like
                # ``json.dumps`` did for the legacy dict form
                self._keys = list(self.index)
        return self._keys

    @classmethod
    def from_frame(cls, frame: pd.DataFrame) -> "WireTable":
        """A columnar view of an existing (MultiIndex-column) response
        frame — the bridge that lets legacy pandas assemblies (custom
        detectors) ride the new wire encoders."""
        columns: List[WireColumn] = []
        if isinstance(frame.columns, pd.MultiIndex):
            for group, sub in frame.columns:
                columns.append(
                    WireColumn(
                        str(group),
                        str(sub) if sub is not None else "",
                        frame[(group, sub)].to_numpy(),
                    )
                )
        else:
            for name in frame.columns:
                columns.append(
                    WireColumn(str(name), "", frame[name].to_numpy())
                )
        return cls(frame.index, columns)

    def groups(self) -> Iterator[Tuple[str, List[WireColumn]]]:
        """Columns grouped by consecutive top-level key, in order."""
        group: Optional[str] = None
        bucket: List[WireColumn] = []
        for column in self.columns:
            if column.group != group:
                if bucket:
                    yield group, bucket  # type: ignore[misc]
                group, bucket = column.group, []
            bucket.append(column)
        if bucket:
            yield group, bucket  # type: ignore[misc]

    def unique_labels(self) -> bool:
        """Whether every (group, sub) label is unique — the fast wire
        encoders require it (the legacy pandas path keeps pandas'
        warn-and-omit duplicate-label semantics)."""
        labels = [(c.group, c.sub) for c in self.columns]
        return len(set(labels)) == len(labels)

    def to_frame(self) -> pd.DataFrame:
        """The equivalent MultiIndex-column DataFrame — the compatibility
        bridge for the legacy parquet wire format (``?format=parquet``
        responses decode to the exact frame the pandas path produced)."""
        data = {(c.group, c.sub): c.values for c in self.columns}
        frame = pd.DataFrame(
            data,
            index=self.index,
            columns=pd.MultiIndex.from_tuples(list(data)),
        )
        return frame

    def to_wire_dict(self) -> dict:
        """The nested ``{group: {sub: {key: value}}}`` wire dict — the
        fleet route's JSON envelope embeds tables per machine. Numeric
        columns go through ``tolist()`` (python scalars, like pandas
        ``to_dict`` produced)."""
        keys = self.keys
        out: dict = {}
        for group, bucket in self.groups():
            # sub '' nests under the group's own name, matching the
            # legacy pandas serializer (('start', '') collapsed to a
            # Series named 'start' and THAT became the wire sub key)
            out[group] = {
                (c.sub or group): dict(
                    zip(
                        keys,
                        c.values.tolist()
                        if isinstance(c.values, np.ndarray)
                        else c.values,
                    )
                )
                for c in bucket
            }
        return out
