"""Pieces of the benchmark found by name, each a file of its own.

``BENCHMARK.json`` sits beside the benchmark's directory ``BENCH`` and
names cells, configurations and metrics; the harness finds every other
piece from a name:

* ``traffic/<traffic>.json`` and ``limits/<cell>.json``: data;
* ``metrics/<metric>.py``: a per-layer reader, ``read(reduced)``;
* ``kinds/<query>.py``: a query kind beyond ``system.KINDS``, a class
  ``Kind`` with the protocol of ``system.Sweep``;
* ``generators/<generator>.py``: a generator beyond
  ``generate.GENERATORS``, ``generate(rng, params, cfg)``.

A module's folder joins ``sys.path`` (after the benchmark's own
modules), so a kind may import a reference of its own that is a new
file beside it.
"""

from __future__ import annotations

import functools
import importlib.util
import sys
from pathlib import Path

#: The benchmark's directory; ``BENCHMARK.json`` is in its parent.
BENCH = Path(__file__).resolve().parent


def load(folder: str, name: str):
    """The module ``<BENCH>/<folder>/<name>.py``, executed once."""
    path = BENCH / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {folder[:-1]} named {name!r} "
                                f"(looked for {path})")
    return _module(path)


@functools.cache
def _module(path: Path):
    if str(path.parent) not in sys.path:
        sys.path.append(str(path.parent))
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod
