"""Finds everything a run needs by the name in BENCHMARK.json.

A cell ``<name>`` is ``workloads/<name>.json``; it names a configuration
(``configs/<config>.json``), a traffic mix (``traffic/<mix>.json``) and a
kind (``kinds/<kind>.py``). A per-layer metric ``<name>`` is
``layer_metrics/<name>.json``, which names a reader
(``readers/<reader>.py``). A later PR adds files and manifest entries and
edits nothing here. An unknown name is an error, never a default.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# what a kind measures end to end, for a cell the manifest does not list yet
E2E_OF_KIND = {
    "train": {"train_tok_s_chip": "tokens/s/chip", "setup_s": "s"},
    "serve": {"serve_tok_s": "tokens/s", "ttft_p95_ms": "ms",
              "tpot_p95_ms": "ms", "setup_s": "s"}}


class UnknownName(Exception):
    pass


def _load(kind_dir: str, name: str) -> dict:
    path = os.path.join(HERE, kind_dir, name + ".json")
    if os.sep in name or not os.path.isfile(path):
        have = sorted(n[:-5] for n in os.listdir(os.path.join(HERE, kind_dir))
                      if n.endswith(".json"))
        raise UnknownName(f"no {kind_dir}/{name}.json; there are: {have}")
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(name: str) -> dict:
    return _load("workloads", name)


def config(name: str) -> dict:
    return _load("configs", name)


def traffic(name: str) -> dict:
    return _load("traffic", name)


def layer_metric(name: str) -> dict:
    return _load("layer_metrics", name)


def _module(package: str, name: str):
    path = os.path.join(HERE, package, name + ".py")
    if not name.isidentifier() or not os.path.isfile(path):
        raise UnknownName(f"no {package}/{name}.py")
    return importlib.import_module(f"benchmark.{package}.{name}")


def kind(name: str):
    return _module("kinds", name)


def reader(name: str):
    return _module("readers", name)


def cell(name: str) -> dict:
    """The cell with its configuration and mix resolved."""
    w = workload(name)
    return {"name": name, **w, "config_name": w["config"],
            "config": config(w["config"]), "mix_name": w["traffic"],
            "mix": traffic(w["traffic"])}


def metrics_for(name: str, section: str, kind_name: str) -> list:
    """The manifest's metrics of ``section`` that cell ``name`` reports:
    those with no ``workloads`` key and those that list the cell. A cell
    the manifest does not list (the CPU rehearsal, a cell being proved)
    takes every end-to-end metric its kind gives and every per-layer
    metric whose file says it is for the cell's kind."""
    man = manifest()
    if any(w["name"] == name for w in man["workloads"]):
        return [m for m in man[section]
                if m.get("workloads") is None or name in m["workloads"]]
    if section == "end_to_end":
        return [{"name": n, "unit": u}
                for n, u in E2E_OF_KIND[kind_name].items()]
    out = []
    for fname in sorted(os.listdir(os.path.join(HERE, "layer_metrics"))):
        spec = layer_metric(fname[:-5])
        if kind_name in spec["kinds"]:
            out.append({"name": fname[:-5], "unit": spec["unit"]})
    return out


def peak(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise UnknownName(f"device kind {device_kind!r} is not in "
                          f"benchmark/peaks.json ({sorted(peaks)})")
    return peaks[device_kind]
