"""Load a Python file of the benchmark that a name selects (an
architecture, an arrival kind, a per-layer metric) as a module."""

from __future__ import annotations

import importlib.util
import re
import sys
from pathlib import Path

__all__ = ["load"]


def load(path: Path):
    """The module in ``path``, executed once per process. A missing file
    is an error that names it."""
    path = Path(path).resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    name = "bench_file_" + re.sub(r"\W", "_", str(path))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]
