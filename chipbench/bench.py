"""What the benchmark is made of, found by name.

`BENCHMARK.json` at the checkout's root names the cells; each cell names
a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); each per-layer metric is a reader of its own
(``metrics/<name>.py``); a configuration names its plain reference
(``reference/<name>.py``), and its published architecture
(``architectures[0]``) names the module that builds its model
(``architectures/<name>.py``). Adding any of them takes a new file and a
new entry, never an edit of an existing file.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def _named(kind: str, name: str, suffix: str,
           here: pathlib.Path = HERE) -> pathlib.Path:
    path = here / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1] if kind.endswith('s') else kind}"
                                f" file {path.relative_to(here.parent)}")
    return path


def config(name: str, here: pathlib.Path = HERE) -> dict:
    return load_json(_named("configs", name, ".json", here))


def traffic(name: str, here: pathlib.Path = HERE) -> dict:
    return load_json(_named("traffic", name, ".json", here))


def limits(workload_name: str, here: pathlib.Path = HERE) -> dict:
    """The limits a cell's outputs are held to, each with the readings
    it was set from (``limits/<workload>.json``)."""
    return load_json(_named("limits", workload_name, ".json", here))


def _module(kind: str, name: str, here: pathlib.Path) -> ModuleType:
    path = _named(kind, name, ".py", here)
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: pathlib.Path = HERE) -> ModuleType:
    """The module that reads per-layer metric ``name``: it has
    ``read(ctx) -> Optional[float]``."""
    return _module("metrics", name, here)


def reference(name: str, here: pathlib.Path = HERE) -> ModuleType:
    """A configuration's plain reference module."""
    return _module("reference", name, here)


#: what an architecture module gives, each a function
ARCHITECTURE = ("model_config", "init_weights", "weight_bytes",
                "prefill_flops", "decode_flops")


def architecture(conf: dict, here: pathlib.Path = HERE) -> ModuleType:
    """The module that builds the model of configuration ``conf``:
    ``architectures/<name>.py`` for its published ``architectures[0]``.
    It gives `ARCHITECTURE`: ``model_config(conf)`` (the program's
    ``ModelConfig``), ``init_weights(cfg, seed)`` (seeded weights as
    served, on the device), ``weight_bytes(cfg)``, and the model
    operations of a served token, ``prefill_flops(cfg, n_pre, s)`` (the
    first token: ``s`` tokens computed after ``n_pre`` in the cache) and
    ``decode_flops(cfg, context)`` (a later one)."""
    name = conf["architectures"][0]
    try:
        mod = _module("architectures", name, here)
    except FileNotFoundError as e:
        raise FileNotFoundError(
            f"{conf['name']}: no module for architecture {name!r}; add "
            f"{here.name}/architectures/{name}.py giving "
            f"{', '.join(ARCHITECTURE)}") from e
    missing = [f for f in ARCHITECTURE if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"{here.name}/architectures/{name}.py lacks "
                             f"{missing}")
    return mod


def metrics_for(bench: dict, workload_name: str, trace: bool) -> List[dict]:
    """The metrics a run of ``workload_name`` reports: end-to-end with
    ``trace`` off, per-layer with it on. A metric with a ``workloads``
    key is reported only in the cells it lists."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload_name in m["workloads"]]


def peaks(device_kind: str, here: pathlib.Path = HERE) -> Dict[str, float]:
    """Published peaks of ``device_kind``; a kind with no entry is an
    error, never a stand-in."""
    table = load_json(here / "peaks.json")
    entry: Optional[dict] = table.get(device_kind)
    if device_kind == "source" or entry is None:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(k for k in table if k != 'source')}")
    return entry
