"""Find the benchmark's parts by name: nothing here names a cell.

- ``configs/<config>.json``: one deployment (scenario, sizes, solver,
  sharding) with its source, ``assumed`` and ``reduced``; its ``"ics"``
  names the scenario, its ``"check"`` the reference and its ``"work"``
  the count of a force evaluation's work (required: a configuration
  without it is refused);
- ``scenarios/<ics>.py``: one scenario's initial conditions, a module
  with ``make(key, cfg) -> (positions, velocities, masses)``;
- ``reference/<check>.py``: one plain reference, a module with
  ``integrate_targets(x0, v0, m, idx, *, steps, ...)`` whose other
  keyword parameters are keys of the configuration's ``simulation``;
- ``work/<work>.py``: the work of one force evaluation as the algorithm
  needs it, whatever kernel does it, a module with
  ``per_eval(cfg, x0, masses) -> {"flops", "bytes", "bound_note"}``;
  the harness counts it after the window of a traced run and hands the
  metrics the window's total (``Facts.flops``, ``Facts.bytes``);
- ``traffic/<traffic>.json``: one mix, read by the harness's one loop,
  with the program's outputs it switches on;
- ``workloads/<cell>.json``: one cell: its config, traffic, chips, why,
  and the two numbers that size its window (``block_steps``,
  ``nominal_step_s``);
- ``metrics/<metric>.py``: one per-layer metric each, a module with
  ``LAYER``, ``UNIT``, ``BETTER``, ``MOVES``, ``SOURCE`` and
  ``read(facts) -> float | None``;
- ``peaks.json``: published peaks keyed by JAX's ``device_kind``.

A later PR adds a cell, a mix or a metric by adding files alone: a new
deployment is a configuration, and, where its physics is new, a
scenario, a reference and a work module.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownDevice(LookupError):
    """A device kind with no published peak in ``peaks.json``."""


def _check(name: str) -> str:
    if not _NAME.match(name or ""):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _load(kind: str, name: str, *, base: str = HERE) -> dict:
    path = os.path.join(base, kind, _check(name) + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def cell(name: str, *, base: str = HERE) -> dict:
    """The cell ``name`` with its config and traffic loaded under
    ``"config_data"`` and ``"traffic_data"``."""
    w = dict(_load("workloads", name, base=base))
    w["name"] = name
    w["config_data"] = _load("configs", w["config"], base=base)
    w["traffic_data"] = _load("traffic", w["traffic"], base=base)
    w["base"] = base
    return w


def module(kind: str, name: str, *, base: str = HERE):
    """The module ``<base>/<kind>/<name>.py``, loaded from its file."""
    path = os.path.join(base, kind, _check(name) + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_modules(*, base: str = HERE) -> dict:
    """Every per-layer metric module under ``metrics/``, by name."""
    mdir = os.path.join(base, "metrics")
    return {fn[:-3]: module("metrics", fn[:-3], base=base)
            for fn in sorted(os.listdir(mdir))
            if fn.endswith(".py") and not fn.startswith("_")}


def benchmark(*, root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def per_layer_for(cell_name: str, *, root: str = ROOT) -> list:
    """Names of the per-layer metrics ``BENCHMARK.json`` asks of this
    cell: those that list it, and those that list no cells."""
    return [m["name"] for m in benchmark(root=root)["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def peaks(device_kind: str, *, base: str = HERE) -> dict:
    with open(os.path.join(base, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no published peak for device kind {device_kind!r} in "
            f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
