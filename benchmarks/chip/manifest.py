"""``BENCHMARK.json`` and the data files it names. Everything that
belongs to one configuration, one traffic mix or one per-layer metric is
a file of its own, found by the name in the manifest:

    configs/<config>.json    traffic/<traffic>.json    metrics/<name>.json

so a later PR adds a cell by adding files and entries and edits none."""

import json
import os


class Cell:
    """One entry of ``workloads`` with its files loaded."""

    def __init__(self, root, manifest, name):
        entry = next((w for w in manifest["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json (has: "
                f"{[w['name'] for w in manifest['workloads']]})")
        self.root = root
        self.manifest = manifest
        self.name = name
        self.chips = entry["chips"]
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        conf = next(c for c in manifest["configs"]
                    if c["name"] == entry["config"])
        self.config = load_json(os.path.join(root, conf["file"]))
        self.traffic = load_json(os.path.join(
            bench_dir(root, manifest), "traffic", entry["traffic"] + ".json"))

    def reports(self, metric):
        """Whether this cell reports ``metric`` (a manifest entry)."""
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.manifest["end_to_end"] if self.reports(m)]

    def per_layer(self):
        """(manifest entry, metric file) for each per-layer metric of
        this cell."""
        mdir = os.path.join(bench_dir(self.root, self.manifest), "metrics")
        return [(m, load_json(os.path.join(mdir, m["name"] + ".json")))
                for m in self.manifest["per_layer"] if self.reports(m)]


def load_json(path):
    with open(path) as f:
        return json.load(f)


def bench_dir(root, manifest):
    """The harness's own directory: the first of ``paths``."""
    return os.path.join(root, manifest["paths"][0])


def load(root):
    return load_json(os.path.join(root, "BENCHMARK.json"))
