"""Small helpers the parent, the workers and the tests share."""

from __future__ import annotations

import importlib.util
import json
import os


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file found by name (a loop, a reference, a metric reader, an
    event handler): the benchmark's files are data-driven, not a package."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def write_atomic(path: str, text: str) -> None:
    """Whoever polls for ``path`` sees it whole or not at all."""
    tmp = os.path.join(os.path.dirname(path), ".tmp." + os.path.basename(path))
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)
