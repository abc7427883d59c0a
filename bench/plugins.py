"""Loads the benchmark's parts by name from their own files.

Every part that belongs to one configuration, traffic process, event kind
or metric is a module of its own, found by the name a data file gives:

* ``bench/builders/<builder>.py``   ``build(**args, seed) -> FleetArrays``
* ``bench/processes/<kind>.py``     ``timeline(b, spec, seconds)``
* ``bench/events/<kind>.py``        ``program_event`` and ``replay``
* ``bench/metrics/<name>.py``       ``read(run)``

so a later cell adds files and entries and edits none that are there.
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def load(group: str, name: str):
    path = BENCH / group / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {group} module {name!r} (looked for {path})")
    spec = importlib.util.spec_from_file_location(f"bench_{group}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
