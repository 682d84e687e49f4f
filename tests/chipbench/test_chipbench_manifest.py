"""BENCHMARK.json keeps the contract's shape, every name in it resolves to its
files, and a later PR can add a configuration, a mix, a job kind and a layer
metric as files plus entries, with no edit to a file that is there."""

import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    return manifest.load_manifest()


def test_manifest_has_exactly_the_contract_keys(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert m["command"] == ["python", "-m", "chipbench"]
    assert m["paths"] == ["chipbench", "tests/chipbench"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines_keep_to_the_contract(m):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and c["source"].isascii()
        assert c["file"].startswith("chipbench/")
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES


def test_cells_pairs_and_chips(m):
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    four = [w["name"] for w in m["workloads"] if w["chips"] == 4]
    assert four == ["resnet50-atc-exp2-4chip"]
    assert {"resnet50-atc-1chip", "bert-base-pushsum-1chip"} <= {
        w["name"] for w in m["workloads"]}


DECODER = "smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip"
CONSTANT_RATE = "smallthinker-21b-a3b-atc-b2-s8k-1chip"
DECODER_METRICS = {
    "train_step_host_ms_per_step", "attention_ms_per_step", "expert_ms_per_step",
    "flash_fwd_window_roofline", "flash_bwd_dkv_window_roofline",
    "flash_bwd_dq_window_roofline"}


def test_the_warm_up_cell_is_the_constant_rate_cell_with_one_change(m):
    cells = {w["name"]: w for w in m["workloads"]}
    assert len(cells) == 6
    assert cells[DECODER] == dict(
        cells[CONSTANT_RATE], name=DECODER, traffic="atc-warmup-b2-s8k-1chip",
        why=cells[DECODER]["why"])
    # the constant-rate cell's mix with one change: the optimizer warms up
    mix, old = manifest.resolve(DECODER).mix, manifest.resolve(CONSTANT_RATE).mix
    assert {k: v for k, v in mix.items() if k != "describes"} == {
        "job": "spmd_train_step", "communication_type": "neighbor_allreduce",
        "mode": "atc", "topology": {"graph": "ExponentialTwoGraph", "kwargs": {}},
        "mixing": "exp2", "pool": 4,
        "optimizer": {"name": "adamw", "learning_rate": 0.0003, "weight_decay": 0.1,
                      "warmup_steps": 2000},
        "sizes": {"per_rank_batch": 2, "seq_len": 8192}}
    assert dict(mix, optimizer=old["optimizer"], describes="") == dict(old, describes="")
    # the configuration is as it was: no warm-up of its own, the same cut
    config = manifest.resolve(DECODER).config
    assert config["optimizer"] == old["optimizer"] == {
        "name": "adamw", "learning_rate": 0.0003, "weight_decay": 0.1}
    assert any("warm-up" in line for line in config["assumed"])


@pytest.mark.parametrize("cell", [CONSTANT_RATE, DECODER])
def test_both_decoder_cells_are_read_by_the_decoders_metrics(m, cell):
    named = {p["name"] for p in m["per_layer"] if cell in p.get("workloads", [])}
    assert named == DECODER_METRICS
    resolved = {p["name"] for p in manifest.resolve(cell).per_layer}
    assert DECODER_METRICS <= resolved


def test_a_name_the_manifest_does_not_have_is_refused_and_never_stood_in_for():
    """No alias in the harness: a cell is found by the name BENCHMARK.json
    gives it, and any other name raises, a near miss too."""
    assert not hasattr(manifest, "RENAMED")
    for name in ("smallthinker-21b-a3b-atc-b2-s8k", DECODER + "-old",
                 "smallthinker-21b-a3b"):
        with pytest.raises(manifest.ManifestError, match="no workload named"):
            manifest.resolve(name)


def test_metrics_bounds_and_what_each_layer_metric_moves(m):
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert set(e2e) == {"train_samples_s_chip", "step_ms_p95", "setup_s"}
    for e in e2e.values():
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in m["workloads"]}
    for p in m["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert p["moves"] in e2e
        assert set(p.get("workloads", cells)) <= cells
    # every cell reports at least one per-layer metric
    for c in cells:
        assert any(c in p.get("workloads", cells) for p in m["per_layer"])


def _states_its_cut(config):
    """A cut is stated, not hidden: each item of `reduced` begins with the key
    of `sizes` it cuts, and the deployment that the cut stands for is named."""
    reduced = config["reduced"]
    assert isinstance(reduced, list)
    for item in reduced:
        assert isinstance(item, str) and any(
            re.match(re.escape(key) + r"\b", item) for key in config["sizes"]), item
    if reduced:
        assert isinstance(config["deployment"], str) and config["deployment"]


def test_every_workload_resolves_to_its_files(m):
    for w in m["workloads"]:
        cell = manifest.resolve(w["name"])
        assert cell.chips == w["chips"]
        assert set(cell.files) == {"reference", "program", "flops", "job", "mixing"}
        assert all(os.path.isfile(f) for f in cell.files.values())
        _states_its_cut(cell.config)
        assert "rehearsal" in cell.config
        assert cell.sizes(rehearse=True) != cell.sizes()
        for metric in cell.per_layer:
            assert callable(cell.reader(metric["name"]).read)
        for metric in cell.end_to_end:
            assert callable(cell.reader(metric["name"], "end_to_end").read)


def test_config_files_state_source_and_published_widths(m):
    for c in m["configs"]:
        cfg = json.load(open(os.path.join(REPO, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["assumed"] and cfg["guarantee"]
    bert = json.load(open(os.path.join(REPO, "chipbench/configs/bert-base.json")))["sizes"]
    assert (bert["vocab_size"], bert["hidden_size"], bert["num_hidden_layers"],
            bert["num_attention_heads"], bert["intermediate_size"],
            bert["max_position_embeddings"]) == (30522, 768, 12, 12, 3072, 512)
    res = json.load(open(os.path.join(REPO, "chipbench/configs/resnet50.json")))["sizes"]
    assert res["stage_sizes"] == [3, 4, 6, 3] and res["num_classes"] == 1000
    assert res["image_size"] == 224 and res["per_rank_batch"] == 128


def test_a_name_without_a_file_is_an_error_that_names_the_path(tmp_path, m):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "chipbench"), root / "chipbench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.remove(root / "chipbench" / "mixing" / "exp2.py")
    with pytest.raises(manifest.ManifestError, match=r"mixing/exp2\.py"):
        manifest.resolve("resnet50-atc-1chip", root=str(root))
    with pytest.raises(manifest.ManifestError, match="no workload named"):
        manifest.resolve("no-such-cell", root=str(root))


def test_a_mix_lays_its_sizes_over_the_configurations(m):
    cell = manifest.resolve("bert-base-atc-b128-1chip")
    config = cell.config["sizes"]
    assert cell.mix["sizes"] == {"per_rank_batch": 128}
    assert cell.sizes() == dict(config, per_rank_batch=128)
    assert config["per_rank_batch"] == 32  # the configuration's file is as it was
    # a rehearsal lays the configuration's tiny block over both
    assert cell.sizes(rehearse=True) == dict(config, **cell.config["rehearsal"])
    # the cells without such a block resolve to the configuration's sizes
    for name in ("resnet50-atc-1chip", "bert-base-pushsum-1chip",
                 "resnet50-atc-exp2-4chip"):
        other = manifest.resolve(name)
        assert "sizes" not in other.mix and other.sizes() == other.config["sizes"]


@pytest.mark.parametrize("block,stray", [
    ({"per_rank_batch": 8, "hidden_size": 64}, "hidden_size"),
    ({"num_hidden_layers": 2}, "num_hidden_layers"),
])
def test_a_mix_may_not_lay_a_width_or_a_depth(tmp_path, m, block, stray):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "chipbench"), root / "chipbench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root / "BENCHMARK.json")
    mix_file = root / "chipbench" / "traffic" / "atc-b128-1chip.json"
    mix = json.loads(mix_file.read_text())
    mix_file.write_text(json.dumps(dict(mix, sizes=block)))
    with pytest.raises(manifest.ManifestError, match=rf"atc-b128-1chip\.json.*{stray}"):
        manifest.resolve("bert-base-atc-b128-1chip", root=str(root))
    mix_file.write_text(json.dumps(dict(mix, sizes={"seq_len": 64})))
    assert manifest.resolve("bert-base-atc-b128-1chip", root=str(root)) \
        .sizes()["seq_len"] == 64


def test_a_later_pr_adds_files_and_entries_and_edits_nothing(tmp_path, m):
    root = tmp_path / "checkout"
    bench = root / "chipbench"
    shutil.copytree(os.path.join(REPO, "chipbench"), bench)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    # one new file of each kind ...
    (bench / "configs" / "toy.json").write_text(json.dumps({
        "name": "toy", "source": "a paper",
        "sizes": {"width": 8, "num_layers": 4, "per_rank_batch": 2},
        "optimizer": {"name": "sgd", "learning_rate": 0.1},
        "reduced": ["num_layers"], "published": {"num_layers": 48},
        "deployment": "4 of 48 layers: one pipeline stage of twelve",
        "assumed": [], "rehearsal": {"width": 2}}))
    for kind in ("reference", "program", "flops"):
        (bench / kind / "toy.py").write_text("MARK = %r\n" % kind)
    (bench / "traffic" / "toy-mix.json").write_text(json.dumps({
        "job": "toy_job", "mixing": "toy_mixing", "sizes": {"per_rank_batch": 6},
        "topology": {"graph": "RingGraph", "kwargs": {}}}))
    (bench / "jobs" / "toy_job.py").write_text("class Job:\n    pass\n")
    (bench / "mixing" / "toy_mixing.py").write_text(
        "import numpy as np\n\n\ndef matrix(n):\n    return np.eye(n)\n")
    (bench / "layer_metrics" / "toy_ms.py").write_text(
        "def read(run):\n    return run.get('toy')\n")
    # ... and entries appended to the manifest
    new = json.loads(json.dumps(m))
    new["configs"].append({"name": "toy", "source": "a paper",
                           "reduced": ["num_layers"],
                           "file": "chipbench/configs/toy.json", "why": "test"})
    new["workloads"].append({"name": "toy-cell", "config": "toy",
                             "traffic": "toy-mix", "chips": 1, "why": "test"})
    new["per_layer"].append({"name": "toy_ms", "unit": "ms", "better": "lower",
                             "source": "program_span", "layer": "train step",
                             "moves": "train_samples_s_chip",
                             "workloads": ["toy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = manifest.resolve("toy-cell", root=str(root))
    assert cell.sizes() == {"width": 8, "num_layers": 4, "per_rank_batch": 6}
    assert cell.sizes(rehearse=True) == {"width": 2, "num_layers": 4,
                                         "per_rank_batch": 6}
    assert cell.config["reduced"] == ["num_layers"]
    _states_its_cut(cell.config)  # as every cell of the manifest is held to
    with pytest.raises(AssertionError):
        _states_its_cut(dict(cell.config, reduced=["layers_kept"]))
    with pytest.raises(KeyError):
        _states_its_cut({k: v for k, v in cell.config.items() if k != "deployment"})
    assert cell.module("job").Job and cell.module("reference").MARK == "reference"
    assert cell.module("mixing").matrix(3).shape == (3, 3)
    names = [p["name"] for p in cell.per_layer]
    assert "toy_ms" in names and "window_device_ms_per_round" not in names
    assert cell.reader("toy_ms").read({"toy": 1.5}) == 1.5
    assert cell.reader("toy_ms").read({}) is None  # nothing to read: left out
    # the old cells still resolve, and no file that was there has changed
    assert manifest.resolve("resnet50-atc-1chip", root=str(root)).chips == 1
    assert all(p.read_bytes() == b for p, b in before.items())
