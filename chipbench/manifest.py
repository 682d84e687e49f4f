"""BENCHMARK.json and the files its names stand for.

Whatever belongs to one configuration, one traffic mix, one job kind or one
per-layer metric is a file of its own under the benchmark's directory, found
by the name the manifest gives.  A name that has no file is an error that
names the missing path.
"""

import importlib.util
import json
import os
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what a traffic mix may lay over its configuration's sizes: the shape of the
# traffic, never a width of the model
MIX_SIZES = ("per_rank_batch", "seq_len")


class ManifestError(Exception):
    """A name in BENCHMARK.json that resolves to nothing."""


def _need(path):
    if not os.path.isfile(path):
        raise ManifestError(f"missing file: {path}")
    return path


def load_json(path):
    with open(_need(path)) as f:
        return json.load(f)


def load_module(path):
    """Import one file by path (file names such as ``bert-base.py`` are not
    importable by name)."""
    _need(path)
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, REPO))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise ManifestError(f"no {what} named {name!r} in BENCHMARK.json (has: {known})")


@dataclass
class Cell:
    """One workload of the manifest with every file it names, resolved."""

    name: str
    chips: int
    root: str            # the checkout
    bench_dir: str       # <root>/<paths[0]>
    config_name: str
    config: dict         # the configuration file as it is run
    mix_name: str
    mix: dict            # the traffic mix's parameters
    end_to_end: list     # manifest entries reported in this cell
    per_layer: list      # manifest entries read in this cell
    files: dict = field(default_factory=dict)  # kind -> path

    def module(self, kind):
        return load_module(self.files[kind])

    def reader(self, metric_name, kind="layer_metrics"):
        """The small reader of one metric: `read(run) -> number or None`."""
        return load_module(os.path.join(self.bench_dir, kind, metric_name + ".py"))

    def sizes(self, rehearse=False):
        """The sizes that are run: the configuration's, the mix's `sizes`
        block laid over them, and in a rehearsal the configuration's
        `rehearsal` block over both, so that a rehearsal stays tiny."""
        sizes = dict(self.config["sizes"])
        sizes.update(self.mix.get("sizes", {}))
        if rehearse:
            sizes.update(self.config["rehearsal"])
        return sizes


def _applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_manifest(root=REPO):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(workload, root=REPO, manifest=None):
    """The cell named ``workload`` with all its files; raises ManifestError
    naming the first path that is missing."""
    manifest = manifest or load_manifest(root)
    w = _entry(manifest["workloads"], workload, "workload")
    c = _entry(manifest["configs"], w["config"], "config")
    bench_dir = os.path.join(root, manifest["paths"][0])
    config = load_json(os.path.join(root, c["file"]))
    mix_path = os.path.join(bench_dir, "traffic", w["traffic"] + ".json")
    mix = load_json(mix_path)
    stray = sorted(set(mix.get("sizes", {})) - set(MIX_SIZES))
    if stray:
        raise ManifestError(
            f"{mix_path}: a mix's `sizes` may hold only {', '.join(MIX_SIZES)}; "
            f"it has {', '.join(stray)}")
    files = {
        "reference": os.path.join(bench_dir, "reference", w["config"] + ".py"),
        "program": os.path.join(bench_dir, "program", w["config"] + ".py"),
        "flops": os.path.join(bench_dir, "flops", w["config"] + ".py"),
        "job": os.path.join(bench_dir, "jobs", mix["job"] + ".py"),
        "mixing": os.path.join(bench_dir, "mixing", mix["mixing"] + ".py"),
    }
    for path in files.values():
        _need(path)
    per_layer = [m for m in manifest["per_layer"] if _applies(m, workload)]
    end_to_end = [m for m in manifest["end_to_end"] if _applies(m, workload)]
    for m in per_layer:
        _need(os.path.join(bench_dir, "layer_metrics", m["name"] + ".py"))
    for m in end_to_end:
        _need(os.path.join(bench_dir, "end_to_end", m["name"] + ".py"))
    return Cell(
        name=workload, chips=int(w["chips"]), root=root, bench_dir=bench_dir,
        config_name=w["config"], config=config, mix_name=w["traffic"], mix=mix,
        end_to_end=end_to_end, per_layer=per_layer, files=files)


def peaks(bench_dir, device_kind):
    """Published peaks of the device; a kind not in the table is an error."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table:
        raise ManifestError(
            f"device kind {device_kind!r} is not in {bench_dir}/peaks.json")
    return table[device_kind]
