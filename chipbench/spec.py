"""Finding a cell's files by the names BENCHMARK.json gives them.

A cell names a configuration (whose entry gives its ``file``) and a traffic
mix; a per-layer metric is found by its own name.  Mixes, readers and
references are looked up under each directory of the benchmark's ``paths``
in turn::

    <path>/traffic/<mix>.json          parameters for chipbench.traffic
    <path>/layer_metrics/<metric>.py   one ``read(run)`` function
    <path>/reference/<file>            the configuration's plain reference

and a configuration's ``driver`` is the module ``chipbench.drivers.<name>``
with one ``run(ctx)`` function.  So a later PR adds a cell by adding files and
entries, and edits nothing.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_benchmark(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r}; known: "
                     f"{[e['name'] for e in entries]}")


def applies(metric: dict, cell: str) -> bool:
    """A metric without ``workloads`` exists in every cell."""
    return "workloads" not in metric or cell in metric["workloads"]


def find(bench: dict, subdir: str, filename: str) -> str:
    tried = []
    for p in bench["paths"]:
        path = os.path.join(ROOT, p, subdir, filename)
        if os.path.isfile(path):
            return path
        tried.append(os.path.relpath(path, ROOT))
    raise SystemExit(f"chipbench: none of {tried} exists")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A Python file by path (metric names carry dots, so readers cannot be
    imported by module name)."""
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(target: str):
    """``"package.module:attribute"`` -> the attribute."""
    module, _, attr = target.partition(":")
    return getattr(importlib.import_module(module), attr)


def model_kwargs(config: dict) -> dict:
    """The model factory's keyword arguments: literal ones, plus those read
    from the configuration's published keys, so a size is written once."""
    m = config["model"]
    return dict(m.get("kwargs", {}),
                **{k: config[key] for k, key in m["kwargs_from"].items()})


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(ROOT, "chipbench", "peaks.json"))
    if device_kind not in table:
        raise SystemExit(
            f"chipbench: no peaks for device_kind {device_kind!r} in "
            f"chipbench/peaks.json (known: {sorted(table)}); a device that "
            f"is not in the table is an error, not a default")
    return table[device_kind]
