"""Find a generator, a per-layer reader or a reference by its name."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(kind: str, name: str):
    """The module ``benchmarks/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind}/{name}.py")
    safe = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{safe}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_data(path: str, rehearse: bool = False) -> dict:
    """A configuration or traffic file; with ``rehearse`` its ``rehearsal``
    group (the tiny sizes of a CPU walk-through) laid over it."""
    with open(path) as f:
        data = json.load(f)
    if rehearse:
        data.update(data.get("rehearsal", {}))
    return data
