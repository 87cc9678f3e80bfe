"""Finding a cell's files by the names `BENCHMARK.json` gives.

Everything that belongs to one cell, one configuration, one traffic
mix or one per-layer metric sits in a data file of its own:

    workloads/<cell>.json        the cell: config, traffic, chips, why,
                                 the cell's own traffic parameters and
                                 the device buckets it warms
    configs/<config>.json        the deployment as it is run; `network`
                                 holds what `e2e.Network` is given
                                 beside the block settings, `reference`
                                 names the module
                                 `references/<reference>.py` that
                                 `correct` is decided by
    traffic/<traffic>.json       the mix; `generator` names the module
                                 `traffic/<generator>.py` that reads it
    layer_metrics/<metric>.json  one per-layer metric; `reducer` names
                                 the module `reducers/<reducer>.py`

A later PR adds a cell or a metric by adding files and appending an
entry to `BENCHMARK.json`; nothing here knows a name.
"""
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


# the rule of a configuration that names none: the one `reference.py`
# held before a configuration could name its own
DEFAULT_REFERENCE = "majority_own_key"


class ManifestError(RuntimeError):
    pass


def load_json(*parts: str, root: str = HERE) -> dict:
    path = os.path.join(root, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def benchmark_json() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`, with the files its names lead to."""

    def __init__(self, name: str, bench: dict = None, root: str = HERE):
        """`bench` and `root` let a test name a manifest and a tree of
        data files of its own (`testdata/`)."""
        bench = bench if bench is not None else benchmark_json()
        entries = [w for w in bench["workloads"] if w["name"] == name]
        if len(entries) != 1:
            raise ManifestError(
                f"BENCHMARK.json has {len(entries)} workloads named "
                f"{name!r}")
        self.entry = entries[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.file = load_json("workloads", name + ".json", root=root)
        for key in ("config", "traffic", "chips"):
            if self.file.get(key) != self.entry[key]:
                raise ManifestError(
                    f"workloads/{name}.json says {key}="
                    f"{self.file.get(key)!r}, BENCHMARK.json says "
                    f"{self.entry[key]!r}")
        self.config = load_json(
            "configs", self.entry["config"] + ".json", root=root)
        self.traffic = load_json(
            "traffic", self.entry["traffic"] + ".json", root=root)
        self.params = dict(self.traffic.get("params", {}))
        self.params.update(self.file.get("params", {}))
        self.end_to_end = [
            e for e in bench["end_to_end"]
            if name in e.get("workloads", [name])]
        self.per_layer = [
            p for p in bench["per_layer"]
            if name in p.get("workloads", [name])]

    def generator(self):
        return importlib.import_module(
            "benchmarks.traffic." + self.traffic["generator"])

    def rule(self):
        """The module of the deployment's rule (`reference.py`)."""
        return importlib.import_module(
            "benchmarks.references."
            + self.config.get("reference", DEFAULT_REFERENCE))


def reducer_for(metric_name: str):
    """(spec, reduce) of one per-layer metric."""
    spec = load_json("layer_metrics", metric_name + ".json")
    mod = importlib.import_module("benchmarks.reducers." + spec["reducer"])
    return spec, mod.reduce
