"""
Dotted-path → object resolution: the primitive under the whole config
language.

Reference parity: gordo-core's ``import_utils.import_location`` (consumed at
gordo/serializer/from_definition.py:16 and throughout); not vendored in the
reference snapshot, so re-derived from its call sites: accepts
``package.module.Attribute`` (and ``package.module:Attribute``), imports the
module, returns the attribute.
"""

import importlib
from typing import Any


def import_location(import_path: str) -> Any:
    """
    Import and return the object at ``import_path``.

    Both ``a.b.Class`` and ``a.b:Class`` forms are accepted. Raises
    ``ImportError`` when the module can't be imported and ``ValueError`` when
    the path is malformed or the attribute is missing.

    Examples
    --------
    >>> import_location("collections.OrderedDict").__name__
    'OrderedDict'
    """
    if not isinstance(import_path, str) or not import_path:
        raise ValueError(f"Invalid import path: {import_path!r}")

    if ":" in import_path:
        module_path, _, attr_path = import_path.partition(":")
        if not module_path or not attr_path:
            raise ValueError(f"Invalid import path: {import_path!r}")
        module = importlib.import_module(module_path)
    else:
        parts = import_path.split(".")
        if len(parts) < 2:
            raise ValueError(
                f"Import path must contain a module and attribute: {import_path!r}"
            )
        module_path, attr_path = ".".join(parts[:-1]), parts[-1]
        try:
            module = importlib.import_module(module_path)
        except ImportError:
            # The penultimate element may itself be an attribute (e.g. a class
            # with a nested attribute); fall back one level.
            if len(parts) < 3:
                raise
            module = importlib.import_module(".".join(parts[:-2]))
            attr_path = ".".join(parts[-2:])

    obj = module
    for attr in attr_path.split("."):
        try:
            obj = getattr(obj, attr)
        except AttributeError as e:
            raise ValueError(f"Could not resolve {import_path!r}: {e}")
    return obj


def prepare_back_compatible_locations(location: str, aliases: dict) -> str:
    """Map a legacy/reference import path onto its gordo-tpu equivalent."""
    return aliases.get(location, location)
