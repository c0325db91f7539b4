"""
Dotted-path → object resolution, under the name the serializer's callers
know. The code is :mod:`gordo_tpu.utils.import_utils`: the dataset layer
resolves provider and dataset types with it and must not import this
package to do so (``serializer/__init__`` brings sklearn, three seconds a
fresh interpreter, which a fetch worker has no use for:
``dataset/fetch_pool.py``).
"""

from ..utils.import_utils import import_location, prepare_back_compatible_locations

__all__ = ["import_location", "prepare_back_compatible_locations"]
