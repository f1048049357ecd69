"""Find a part of the benchmark by its name: ``<kind>/<name>.py`` under
this directory, or a named root's ``benchmarks/chip``. Every part that
one configuration, traffic mix or metric brings is such a file, so a new
one is a new file and edits none that exists.

Kinds:

* ``generators/<generator>.py``: ``generate(cfg, V, E, rng)``, a
  configuration's graph (``ustream.make_graph``);
* ``backends/<backend>.py``: ``store_kwargs(cfg)`` (the capacity rule)
  and ``make_store(api, jax, cfg, chips, kwargs)``;
* ``loops/<loop>.py``: ``warmup(drive)`` and ``window(drive, win,
  seconds)``, how a traffic mix's rounds are driven;
* ``e2e/<metric>.py``: ``read(win)``, an end-to-end metric from the
  window's host-clock record;
* ``layers/<metric>.py``, or ``layers/<quantity>.py`` for a metric named
  ``<quantity>.<suffix>``: ``read(win)``, a per-layer metric, or None
  when its trace events or counters are not in the run.
"""
from __future__ import annotations

import importlib.util
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
_LOADED = {}


def load(kind: str, name: str, bench: pathlib.Path = HERE,
         suffix_fallback: bool = False):
    """The module ``bench/<kind>/<name>.py``; with ``suffix_fallback``, a
    name ``<quantity>.<suffix>`` that has no file of its own takes
    ``<quantity>.py``."""
    stems = [name]
    if suffix_fallback and "." in name:
        stems.append(name.split(".")[0])
    for stem in stems:
        path = (bench / kind / f"{stem}.py").resolve()
        if path.is_file():
            if path not in _LOADED:
                spec = importlib.util.spec_from_file_location(
                    f"bench_{kind}_{stem.replace('.', '_')}", path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                _LOADED[path] = mod
            return _LOADED[path]
    raise FileNotFoundError(f"no {kind} part named {name!r} under "
                            f"{bench / kind}")
