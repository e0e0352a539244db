"""The benchmark's parts found by name: ``<kind>/<name>.py`` under
``benchmark/``, where a kind is ``metrics``, ``row_kinds``,
``tree_builders`` or ``references``.  A later cell brings its parts as new
files; nothing here or in the harness names one."""

from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, name: str):
    """A fresh module from ``benchmark/<kind>/<name>.py``."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise LookupError(f"no {kind}/{name}.py under {HERE}")
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", f"bench_{kind}_{name}"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
