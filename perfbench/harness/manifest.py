"""``BENCHMARK.json`` and the files it names: everything that belongs to
one configuration, one model family, one traffic mix, one per-layer metric
or one driver sits in a file of its own, found by the name in the manifest
(the family by the ``"family"`` its configuration file states:
``perfbench/families/<family>.py``, everything that is the model's)."""
from __future__ import annotations

import functools
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "perfbench")


def load(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; there are "
                   f"{[e['name'] for e in entries]}")


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by its path (file names may hold ``.`` and ``-``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def load_family(path: str):
    """A family file, loaded once a process like any import: it holds
    jitted functions, and a second copy would compile them again."""
    name = os.path.splitext(os.path.basename(path))[0]
    return load_module(path, "perfbench_family_" + "".join(
        c if c.isalnum() else "_" for c in name))


class Cell:
    """One entry of ``workloads`` with everything found for it."""

    def __init__(self, manifest: dict, workload: str, root: str = ROOT,
                 data_dir: str = None):
        # ``data_dir`` lets a test bring tiny traffic and limits of its
        # own, and families beside the benchmark's; drivers and metric
        # readers are always the benchmark's.
        bench_dir = BENCH_DIR
        data_dir = data_dir or bench_dir
        self.manifest = manifest
        self.entry = _by_name(manifest["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg = _by_name(manifest["configs"], self.entry["config"], "config")
        self.config = cfg
        self.model = load_json(os.path.join(root, cfg["file"]))
        own = os.path.join(data_dir, "families", self.model["family"] + ".py")
        self.family_path = own if os.path.isfile(own) else os.path.join(
            bench_dir, "families", self.model["family"] + ".py")
        self.traffic = load_json(os.path.join(
            data_dir, "traffic", self.entry["traffic"] + ".json"))
        limits = os.path.join(data_dir, "limits", workload + ".json")
        self.limits = load_json(limits)
        self.driver_path = os.path.join(
            bench_dir, "drivers", self.traffic["driver"] + ".py")
        self.bench_dir = bench_dir

    def _mine(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def end_to_end(self):
        return [m for m in self.manifest["end_to_end"] if self._mine(m)]

    @property
    def per_layer(self):
        reported = {m["name"] for m in self.end_to_end}
        return [m for m in self.manifest["per_layer"]
                if self._mine(m) and m["moves"] in reported]

    def family(self):
        return load_family(self.family_path)

    def driver(self):
        return load_module(self.driver_path,
                           "perfbench_driver_" + self.traffic["driver"].replace("-", "_"))

    def reader(self, metric_name: str):
        path = os.path.join(self.bench_dir, "metrics", metric_name + ".py")
        return load_module(path, "perfbench_metric_" + "".join(
            c if c.isalnum() else "_" for c in metric_name))
