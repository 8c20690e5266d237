"""Finding a cell's files by the names in ``BENCHMARK.json``. Stdlib only:
the parent uses it and stays off JAX."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MODELS = os.path.join(HERE, "models")   # where a model class's files lie

# What a model class answers (benchmarks/README.md has the contract):
# ``<key>.py``, stdlib at import, the parent reads it ...
MODEL_CLASS = ("build", "first_grad_norms", "change_norms_fn", "change_norms",
               "flops_per_token", "attention_layers", "tiny")
# ... and ``<key>_reference.py``, JAX and nothing of the program.
MODEL_REFERENCE = ("leaves", "layer_prefix", "layer_kind", "block")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell(bench: dict, workload: str) -> tuple:
    """(workload entry, configuration dict, traffic dict)."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return (entry, load_json(ROOT, config["file"]),
            load_json(HERE, "traffic", entry["traffic"] + ".json"))


def load_module(folder: str, name: str):
    """The module ``<folder>/<name>.py``, ``folder`` taken from
    ``benchmarks/`` unless it is a path of its own; names may hold dots."""
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{os.path.basename(folder)}.{name.replace('.', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _answering(module, functions: tuple):
    for function in functions:
        if not callable(getattr(module, function, None)):
            raise AttributeError(
                f"model class file {module.__file__} lacks {function}(): "
                "benchmarks/README.md has the contract of a model class")
    return module


def model_class(cfg: dict):
    """The configuration's model class, ``models/<key>.py``."""
    return _answering(load_module(MODELS, cfg["model"]), MODEL_CLASS)


def model_reference(cfg: dict):
    """The class's plain reference, ``models/<key>_reference.py``."""
    return _answering(load_module(MODELS, cfg["model"] + "_reference"),
                      MODEL_REFERENCE)


def metrics_of(bench: dict, workload: str, group: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


class Report:
    """JSON lines the worker appends and the parent reads back."""

    def __init__(self, path: str):
        self.path = path

    def emit(self, **record) -> None:
        with open(self.path, "a") as f:
            f.write(json.dumps(record) + "\n")

    def read(self) -> list:
        try:
            with open(self.path) as f:
                return [json.loads(line) for line in f if line.strip()]
        except FileNotFoundError:
            return []
