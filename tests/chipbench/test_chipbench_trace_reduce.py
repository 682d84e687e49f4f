"""trace_reduce's interval arithmetic on synthetic event lists, and the
reduction of a small trace recorded on the chip (tests/chipbench/data)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import manifest, spans, trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tiny_v5e.xplane.pb")


def test_merge_and_union_length():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (5, 5)]) == [(0, 2.5), (3, 4)]
    assert tr.union_length([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert tr.union_length([]) == 0


def test_subtract_clip_and_gaps():
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (2, 3)], [(0, 5)]) == []
    assert tr.subtract([(0, 1)], []) == [(0, 1)]
    assert tr.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_exposed_collective_time_is_what_no_compute_op_covers():
    collectives = [(0.0, 4.0), (10.0, 11.0)]
    compute = [(1.0, 2.0), (3.0, 3.5), (9.0, 12.0)]
    # [0,1) + [2,3) + [3.5,4) exposed; the second collective is hidden
    assert tr.exposed_length(collectives, compute) == pytest.approx(2.5)
    assert tr.exposed_length(collectives, []) == pytest.approx(5.0)


def test_split_steps_drops_the_edges():
    starts = [0.0, 1.0, 2.0, 3.0, 4.0]
    assert tr.split_steps(starts) == [(1.0, 2.0), (2.0, 3.0)]
    assert tr.split_steps(starts, drop_edges=False)[0] == (0.0, 1.0)
    assert tr.split_steps([0.0, 1.0]) == [(0.0, 1.0)]


def test_gaps_are_named_by_the_innermost_host_span():
    host = [("dispatch", 0.0, 10.0), ("window_op", 2.0, 4.0), ("block", 10.0, 20.0)]
    assert tr.covering_span(3.0, host) == "window_op"
    assert tr.covering_span(5.0, host) == "dispatch"
    assert tr.covering_span(25.0, host) == "(no chipbench span)"
    got = tr.attribute_gaps([(2.5, 3.5), (6.0, 6.5), (12.0, 15.0), (30.0, 31.0)], host)
    assert got == [["block", 3.0], ["window_op", 1.0], ["(no chipbench span)", 1.0],
                   ["dispatch", 0.5]]


def _synthetic(devices=2, steps=6, slow=1):
    """`steps` steps of 10 ms on each device: 6 ms of compute, then a
    collective of 3 ms (4 ms on the slow device) of which the last 1 ms is
    covered by 1 ms more compute; 1 ms idle closes the step."""
    trace = {"devices": {}, "host": []}
    for d in range(devices):
        coll = 0.004 if d == slow else 0.003
        mods, ops, asy = [], [], []
        for k in range(steps):
            t = k * 0.010
            mods.append(("jit_local_step(1)", t, t + 0.009))
            ops.append(("%fusion.1 = f32[8] fusion()", t, t + 0.006))
            ops.append(("%collective-permute-start.1 = f32[8]", t + 0.006, t + 0.0061))
            asy.append(("%collective-permute-start.1 = f32[8]", t + 0.006, t + 0.006 + coll))
            ops.append(("%fusion.2 = f32[8] fusion()", t + 0.005 + coll, t + 0.006 + coll))
            trace["host"].append(("dispatch", t, t + 0.002))
            trace["host"].append(("block", t + 0.002, t + 0.010))
        trace["devices"][d] = {"modules": mods, "ops": ops, "async": asy}
    return trace


def test_reduce_takes_the_median_step_of_the_slowest_device():
    r = tr.reduce(_synthetic(), r"^jit_local_step")
    assert r["devices"] == 2 and r["steps"] == 3  # 6 anchors, 5 steps, edges dropped
    assert r["compute_ms_per_step"] == pytest.approx(7.0)
    assert r["collective_ms_per_step"] == pytest.approx(4.0)   # the slow device's
    assert r["collective_exposed_ms_per_step"] == pytest.approx(3.0)
    assert r["launches_per_round"] == 1
    assert r["window_device_ms_per_round"] == 0
    # device 0: 9 of every 10 ms busy; device 1: all 10 (its last idle ms too)
    assert r["window_s"] == pytest.approx(0.060)
    assert 0.9 * r["window_s"] < r["busy_s"] <= r["window_s"]
    assert r["breakdown"]["device_ops"][0][0].startswith("%fusion.1")
    assert r["breakdown"]["device_ops"][0][1] == pytest.approx(0.036)
    assert r["breakdown"]["idle_gaps"][0][0] == "block"
    assert len(r["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("nested,compute_ms", [
    ([], 7.0),
    # a loop of 4 ms whose two body ops (1.5 ms each) run inside it, on top of
    # the step's 6 ms fusion: the loop and its body are one stretch of the
    # device's time, not 4 + 3 ms beside the 7
    ([("%while.3 = (s32[], f32[8]) while()", 0.001, 0.005),
      ("%fusion.7 = f32[8] fusion()", 0.001, 0.0025),
      ("%fusion.8 = f32[8] fusion()", 0.003, 0.0045)], 7.0),
    # a loop that runs where nothing else does adds its own length, once
    ([("%while.3 = (s32[], f32[8]) while()", 0.0065, 0.0075),
      ("%fusion.7 = f32[8] fusion()", 0.0065, 0.0075)], 8.0),
    # and one that runs past the step's end is clipped to the step
    ([("%while.3 = (s32[], f32[8]) while()", 0.009, 0.012),
      ("%fusion.7 = f32[8] fusion()", 0.009, 0.0095)], 8.0),
], ids=["flat", "loop-inside", "loop-alone", "loop-past-the-end"])
def test_compute_is_the_union_of_a_loop_and_the_ops_inside_it(nested, compute_ms):
    trace = _synthetic(devices=1, slow=None)
    for k in range(6):
        trace["devices"][0]["ops"] += [(n, k * 0.010 + s, k * 0.010 + e)
                                       for n, s, e in nested]
    r = tr.reduce(trace, r"^jit_local_step")
    assert r["compute_ms_per_step"] == pytest.approx(compute_ms)
    assert r["compute_ms_per_step"] <= 10.0  # never more than the step
    # by name each op keeps its own time: a reader of one kernel is not cut
    if nested:
        assert r["ops_ms_per_step"][nested[0][0]] == pytest.approx(
            1e3 * (min(nested[0][2], 0.010) - nested[0][1]))


def test_reduce_counts_window_programs_and_launches_per_round():
    mods, ops = [], []
    for k in range(5):
        t = k * 0.010
        for j, name in enumerate(("jit_rank_loss(1)", "jit_pack(2)", "jit__combine(3)",
                                  "jit_unpack(4)")):
            mods.append((name, t + j * 0.002, t + j * 0.002 + 0.001))
            ops.append(("%op", t + j * 0.002, t + j * 0.002 + 0.001))
    r = tr.reduce({"devices": {0: {"modules": mods, "ops": ops, "async": []}},
                   "host": []}, r"^jit_rank_loss",
                  (r"^jit_pack", r"^jit_unpack", r"^jit__combine"))
    assert r["launches_per_round"] == 4
    assert r["window_device_ms_per_round"] == pytest.approx(3.0)
    assert r["collective_ms_per_step"] == 0


def test_ops_ms_per_step_is_the_median_step_of_the_slowest_device_by_name():
    r = tr.reduce(_synthetic(), r"^jit_local_step")
    ops = r["ops_ms_per_step"]
    assert list(ops) == sorted(ops) and len(ops) == 3
    assert ops["%fusion.1 = f32[8] fusion()"] == pytest.approx(6.0)
    assert ops["%fusion.2 = f32[8] fusion()"] == pytest.approx(1.0)
    # a collective's own op, not its 3-4 ms in flight (the `async` line)
    assert ops["%collective-permute-start.1 = f32[8]"] == pytest.approx(0.1)
    # names are cut as `breakdown` cuts them; an op that runs in one step of
    # the three kept reads the median, 0; one that runs past the step's end
    # is clipped to the step
    trace = _synthetic(devices=1)
    long_name = "%fusion.9 = " + "x" * 200
    trace["devices"][0]["ops"] += [(long_name, 0.0225, 0.0226),
                                   ("%late = f32[]", 0.0395, 0.0415)]
    ops = tr.reduce(trace, r"^jit_local_step")["ops_ms_per_step"]
    assert ops[long_name[:96]] == 0 and long_name not in ops
    assert ops["%late = f32[]"] == 0  # one step of three: the median is 0
    two = _synthetic(devices=1, steps=3)  # two whole steps: both kept
    two["devices"][0]["ops"].append(("%late = f32[]", 0.0095, 0.0115))
    assert tr.reduce(two, r"^jit_local_step")["ops_ms_per_step"]["%late = f32[]"] \
        == pytest.approx(0.25)  # (0.5 ms inside its step + 0) / 2


def test_reduce_without_a_device_plane_finds_nothing():
    assert tr.reduce({"devices": {}, "host": []}, r"^x") is None


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded trace in tests/chipbench/data")
def test_reduce_reads_a_trace_recorded_on_the_chip():
    assert os.path.getsize(RECORDED) < 2 * 1024 * 1024
    trace = tr.load(RECORDED, spans.NAMES)
    assert list(trace["devices"]) == [0]
    dev = trace["devices"][0]
    # eight steps of record_trace's tiny program: the step and the reshape of
    # its loss, two launches each
    names = [n.split("(")[0] for n, _, _ in dev["modules"]]
    assert names == ["jit_tiny_step", "jit_reshape"] * 8
    assert len(dev["ops"]) == 32 and len(dev["async"]) == 8
    assert {n for n, _, _ in trace["host"]} == {"dispatch", "block"}
    r = tr.reduce(trace, r"^jit_tiny_step")
    assert r["steps"] == 5 and r["launches_per_round"] == 2
    assert r["compute_ms_per_step"] == pytest.approx(0.009, rel=0.05)
    assert r["breakdown"]["idle_gaps"][0][0] == "dispatch"  # a tiny step is host-bound
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["compute_ms_per_step"] > 0 and r["collective_ms_per_step"] == 0
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded trace in tests/chipbench/data")
def test_every_value_read_before_ops_ms_per_step_is_unchanged_to_the_last_digit():
    """tiny_v5e.reduced.json is `reduce` of the recorded trace as the commit
    before `ops_ms_per_step` computed it (PR 27's tree)."""
    r = tr.reduce(tr.load(RECORDED, spans.NAMES), r"^jit_tiny_step")
    by_name = r.pop("ops_ms_per_step")
    with open(RECORDED.replace(".xplane.pb", ".reduced.json")) as f:
        before = json.load(f)
    assert json.loads(json.dumps(r)) == before
    # over names it sums to the compute of a step plus the collectives' own
    # ops (none in this program), within the rounding of a median per name
    assert r["collective_ms_per_step"] == 0
    assert sum(by_name.values()) == pytest.approx(r["compute_ms_per_step"], rel=5e-3)
    assert len(by_name) == 4 and all(len(name) <= 96 for name in by_name)


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded trace in tests/chipbench/data")
def test_a_reader_added_as_a_file_sees_a_kernel_by_name(tmp_path):
    """What a later PR's `<kernel>_roofline` reader does: look the kernel up
    by name among all the ops of a step, not among the ten largest."""
    reader = tmp_path / "toy_kernel_ms.py"
    reader.write_text(
        "def read(run):\n"
        "    ops = (run['trace'] or {}).get('ops_ms_per_step', {})\n"
        "    found = [ms for name, ms in ops.items() if name.startswith(run['kernel'])]\n"
        "    return sum(found) if found else None\n")
    read = manifest.load_module(str(reader)).read
    trace = tr.reduce(tr.load(RECORDED, spans.NAMES), r"^jit_tiny_step")
    assert read({"trace": trace, "kernel": "%convolution_tanh_fusion"}) \
        == pytest.approx(0.00388, rel=0.02)
    assert read({"trace": trace, "kernel": "%copy-start"}) == pytest.approx(1.3e-5, rel=0.2)
    assert read({"trace": trace, "kernel": "%no_such_kernel"}) is None
    assert read({"trace": None, "kernel": "%copy-start"}) is None
