"""The readers of the program's own spans (`chipbench/program_spans.py` and
the five `layer_metrics/` files over it): the arithmetic on synthetic span
lists, and two `--rehearse --trace 1` children whose result lines carry the
new metrics.  A rehearsal's values are CPU numbers at toy sizes: only the
counts are compared."""

import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bluefog_tpu import timeline  # noqa: E402
from chipbench import manifest  # noqa: E402

WINDOW_METRICS = ("window_host_ms_per_round", "window_host_self_ms_per_round",
                  "window_programs_per_round", "window_deposit_mb_per_round")
STEP_METRIC = "train_step_host_ms_per_step"


def _round(t, ids, deposit, scale=1.0):
    """One push-sum round starting at t seconds: four window ops, three
    children, every duration `scale` times the plain round's."""
    S, ms = timeline.Span, 1e-3 * scale
    a, u, p, e = (next(ids) for _ in range(4))
    return [
        S(a, None, "win_accumulate", t, t + 2.0 * ms, deposit),
        S(next(ids), a, "win_accumulate/exchange", t + 0.5 * ms, t + 1.5 * ms, 0),
        S(u, None, "win_update", t + 3.0 * ms, t + 6.0 * ms, 0),
        S(next(ids), u, "win_update/combine", t + 3.5 * ms, t + 4.5 * ms, 0),
        # overlaps the combine's end and runs past the parent: counted once,
        # and only inside the parent
        S(next(ids), u, "win_update/reset", t + 4.0 * ms, t + 6.5 * ms, 0),
        S(p, None, "win_associated_p", t + 7.0 * ms, t + 7.25 * ms, 0),
        S(e, None, "win_set_exposed", t + 8.0 * ms, t + 8.5 * ms, deposit),
        # not a window op: belongs to no sum
        S(next(ids), None, "allreduce", t + 9.0 * ms, t + 9.5 * ms, 0),
    ]


def _synthetic():
    """Five whole rounds between six anchors: the first and the last are
    dropped, the three left have scales 1, 1, 3 (median: the plain round);
    the edges, and what follows the last anchor, are made far off so that
    keeping one would show."""
    ids = iter(range(1, 1000))
    spans = []
    for k, scale in enumerate((50.0, 1.0, 1.0, 3.0, 40.0, 60.0)):
        spans += _round(10.0 + k, ids, deposit=2_000_000, scale=scale)
    S = timeline.Span
    for k, dur in enumerate((0.5, 0.002, 0.003, 0.004, 0.4, 0.6)):
        spans.append(S(next(ids), None, "train_step", 20.0 + k, 20.0 + k + dur, 0))
    return spans


# plain round: tops 2 + 3 + 0.25 + 0.5 ms; children cover 1 ms of
# win_accumulate and 2.5 ms (3.5..6.0 inside the parent) of win_update
EXPECTED = {
    "window_host_ms_per_round": 5.75,
    "window_host_self_ms_per_round": 5.75 - 1.0 - 2.5,
    "window_programs_per_round": 3,
    "window_deposit_mb_per_round": 2.0,
    "train_step_host_ms_per_step": 3.0,
}


def _reader(name):
    return manifest.load_module(
        os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_drops_the_edges_and_takes_the_median(name, monkeypatch):
    spans = _synthetic()
    monkeypatch.setattr(timeline, "spans", lambda: list(reversed(spans)))
    value = _reader(name).read({"rehearse": False, "trace": None})
    assert value == pytest.approx(EXPECTED[name], rel=1e-9)
    if name == "window_programs_per_round":
        assert isinstance(value, int)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_its_anchor_finds_nothing(name, monkeypatch):
    anchor = "train_step" if name == STEP_METRIC else "win_accumulate"
    others = [s for s in _synthetic() if s.name != anchor]
    for spans in (others, []):
        monkeypatch.setattr(timeline, "spans", lambda spans=spans: spans)
        assert _reader(name).read({"rehearse": False, "trace": None}) is None


def test_readers_find_nothing_in_a_program_without_the_recorder(monkeypatch):
    """The parent of PR 26 has no `timeline.spans`: no value, no error."""
    monkeypatch.delattr(timeline, "spans")
    for name in EXPECTED:
        assert _reader(name).read({"rehearse": False, "trace": None}) is None


def test_two_whole_intervals_keep_their_edges(monkeypatch):
    """As trace_reduce.split_steps: with no more than two there is no middle."""
    S = timeline.Span
    spans = [S(i + 1, None, "train_step", float(i), i + d, 0)
             for i, d in enumerate((0.002, 0.004, 0.1))]
    monkeypatch.setattr(timeline, "spans", lambda: spans)
    assert _reader(STEP_METRIC).read({}) == pytest.approx(3.0)


def test_new_entries_name_layer_source_moves_and_cells():
    """Found by name, wherever a later PR's entries put them."""
    bench = manifest.load_manifest()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    push = ["bert-base-pushsum-1chip"]
    for name in WINDOW_METRICS:
        m = per_layer[name]
        assert (m["layer"], m["moves"], m["workloads"], m["better"]) == (
            "eager ops, windows", "train_samples_s_chip", push, "lower")
        assert m["source"] == ("program_counter" if m["unit"] in ("count", "MB")
                               else "program_span")
    m = per_layer[STEP_METRIC]
    assert (m["layer"], m["moves"], m["source"], m["unit"]) == (
        "train step", "step_ms_p95", "program_span", "ms")
    # read in every cell whose job kind says its step records the span
    records = {w["name"] for w in bench["workloads"] if "train_step" in getattr(
        manifest.resolve(w["name"]).module("job"), "PROGRAM_SPANS", ())}
    assert set(m["workloads"]) == records
    assert {"resnet50-atc-1chip", "resnet50-atc-exp2-4chip",
            "bert-base-atc-b128-1chip"} <= records
    assert "bert-base-pushsum-1chip" not in records


def _chipbench(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("BENCH_RUN", None)
    env.pop("BLUEFOG_TIMELINE", None)
    proc = subprocess.run([sys.executable, "-m", "chipbench", *args], cwd=REPO,
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_traced_pushsum_rehearsal_carries_the_four_window_metrics():
    result = _chipbench("--workload", "bert-base-pushsum-1chip", "--seed",
                        str(2**31 + 26), "--seconds", "1", "--trace", "1",
                        "--rehearse")
    assert result["correct"] is True
    got = {name: result["metrics"][name]["value"] for name in WINDOW_METRICS}
    assert all(math.isfinite(v) and v > 0 for v in got.values())
    assert STEP_METRIC not in result["metrics"]
    assert got["window_host_self_ms_per_round"] <= got["window_host_ms_per_round"]
    # exchange, combine, reset; a whole number, printed as one
    assert got["window_programs_per_round"] == 3
    assert isinstance(got["window_programs_per_round"], int)
    # the rehearsal's one packed f32 window, from the shapes it runs
    cell = manifest.resolve("bert-base-pushsum-1chip")
    shapes, _ = cell.module("reference").param_shapes(cell.sizes(rehearse=True))
    n_params = sum(math.prod(shape) for shape in shapes.values())
    assert got["window_deposit_mb_per_round"] == n_params * 4 / 1e6


def test_traced_four_rank_rehearsal_carries_the_train_step_span():
    result = _chipbench("--workload", "resnet50-atc-exp2-4chip", "--seed", "26",
                        "--seconds", "1", "--trace", "1", "--rehearse", devices=4)
    assert result["correct"] is True and result["device"]["count"] == 4
    value = result["metrics"][STEP_METRIC]["value"]
    assert math.isfinite(value) and value > 0
    assert result["metrics"][STEP_METRIC]["unit"] == "ms"
    assert not set(WINDOW_METRICS) & set(result["metrics"])
