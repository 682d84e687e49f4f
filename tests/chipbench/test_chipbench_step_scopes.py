"""chipbench/step_scopes.py on synthetic records and traces: the join by
instruction name, a loop's own time, the partition, which program is taken,
and the eleven readers and manifest entries that rest on it."""

import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bluefog_tpu import timeline  # noqa: E402
from bluefog_tpu.timeline import ScopedOp, StepProgram  # noqa: E402
from chipbench import manifest, step_scopes  # noqa: E402

GRANITE = "granite-4.0-h-micro-atc-warmup-b1-s8k-1chip"
LAGUNA = "laguna-xs.2-atc-warmup-b1-s8k-1chip"
RESNETS = ["resnet50-atc-1chip", "resnet50-atc-exp2-4chip"]
# name -> (layer, the cells that list it)
NEW = {
    "optimizer_ms_per_step": ("optimizer + gossip",
                              RESNETS + ["bert-base-atc-b128-1chip", LAGUNA, GRANITE]),
    "gossip_combine_ms_per_step": ("optimizer + gossip", RESNETS[1:]),
    "head_loss_ms_per_step": ("train step", [LAGUNA, GRANITE]),
    "mlp_ms_per_step": ("train step", [LAGUNA, GRANITE]),
    "attention_proj_ms_per_step": ("train step", [LAGUNA, GRANITE]),
    "expert_dispatch_ms_per_step": ("kernels", [LAGUNA]),
    "ssm_mixer_ms_per_step": ("train step", [GRANITE]),
    "recompute_ms_per_step": ("train step", [GRANITE]),
    "conv_ms_per_step": ("train step", RESNETS),
    "batch_norm_ms_per_step": ("train step", RESNETS),
    "unscoped_ms_per_step": ("device", RESNETS + [LAGUNA, GRANITE]),
}
FWD = "jit(local_step)/forward_backward/jvp(M)/"
BWD = "jit(local_step)/forward_backward/transpose(jvp(M))/"
AGAIN = BWD + "forward_backward/jvp(M)/checkpoint/rematted_computation/"


def _op(name, path="", within=None):
    return ScopedOp(name, path, within, timeline.BACKWARD_MARK in path,
                    timeline.RECOMPUTED_MARK in path)


STEP = StepProgram("jit_local_step", (
    _op("param.1", "params['w']"),
    _op("fusion.1", FWD + "layers_0/q/dot_general"),
    _op("fusion.2", FWD + "layers_0/attention_rotary/mul"),
    _op("attention_global.5", FWD + "layers_0/attention_global/pallas_call"),
    _op("fusion.3", BWD + "layers_0/attention_global/reduce_sum"),
    _op("flash_bwd_dq_window.3", BWD + "layers_1/attention_window/pallas_call"),
    _op("ssd_chunk_fwd.21", AGAIN + "layer_0/mixer/ssm_scan/pallas_call"),
    _op("fusion.4", AGAIN + "layer_0/mixer/ssm_in_proj/in_proj/dot_general"),
    _op("while.27", FWD + "layers_1/moe_experts/while"),
    _op("ragged-dot.2", FWD + "layers_1/moe_experts/while/body/ragged_dot", "while.27"),
    _op("fusion.5", FWD + "layers_1/moe_experts/while/body/gather", "while.27"),
    _op("while.28", FWD + "layers_1/moe_experts/while/body/while", "while.27"),
    _op("fusion.6", FWD + "layers_1/moe_experts/while/body/while/body/add", "while.28"),
    _op("while.31", FWD + "lm_head_loss/while"),
    _op("fusion.7", FWD + "lm_head_loss/while/body/dot_general", "while.31"),
    _op("fusion.8", "jit(local_step)/optimizer_update/add"),
    _op("fusion.9", "jit(local_step)/gossip_combine/mul"),
    _op("collective-permute-start.1", "jit(local_step)/gossip_combine/ppermute"),
    _op("fusion.10", FWD + "layers_1/mlp_dense/mlp/dot_general"),
    _op("fusion.11", FWD + "BottleneckBlock_0/Conv_2/conv_general_dilated"),
    _op("fusion.12", BWD + "bn_init/reduce_sum"),
    _op("fusion.13", FWD + "layers_1/add"),
    _op("copy-done.4"),
    _op("fusion.99", FWD + "layers_0/o/dot_general"),  # not in the trace
))
# device ms a step under the start of each op's HLO text, as trace_reduce cuts it
OPS_MS = {
    "%fusion.1 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion(%p)": 4.0,
    "%fusion.2 = bf16[8192,2048]{1,0} fusion(%fusion.1)": 1.0,
    "%attention_global.5 = (bf16[8,8192,128]{2,1,0}, f32[8]) custom-call(%a)": 5.0,
    "%fusion.3 = f32[8192]{0} fusion(%b)": 0.5,
    "%flash_bwd_dq_window.3 = bf16[8,8192,128]{2,1,0} custom-call(%c)": 3.0,
    "%ssd_chunk_fwd.21 = (bf16[1,8192,4096]{2,1,0}, f32[1,32]) custom-call(%d)": 1.5,
    "%fusion.4 = bf16[8192,8512]{1,0} fusion(%e)": 2.5,
    "%while.27 = (s32[]{:T(128)}, f32[8192,16,128]{2,1,0}) while(%t), condition=%c": 9.0,
    "%ragged-dot.2 = bf16[8192,512]{1,0} ragged-dot(%x, %w, %g)": 4.0,
    "%fusion.5 = bf16[8192,2048]{1,0} fusion(%x, %i)": 2.0,
    "%while.28 = (s32[], f32[8]{0}) while(%u), condition=%c2, body=%b2": 1.0,
    "%fusion.6 = f32[8]{0} fusion(%v)": 0.75,
    "%while.31 = (s32[]{:T(128)}, f32[2048,12544]{1,0}) while(%h)": 8.0,
    "%fusion.7 = f32[1024,12544]{1,0} fusion(%h2)": 7.5,
    "%fusion.8 = (f32[1,2048,8192]{2,1,0}, f32[1,2048,8192]{2,1,0}) fusion(%m, %n)": 6.0,
    "%fusion.9 = f32[25000000]{0} fusion(%bucket)": 0.25,
    "%collective-permute-start.1 = f32[25000000]{0} collective-permute-start(%z)": 11.0,
    "%fusion.10 = bf16[8192,16384]{1,0} fusion(%m2)": 10.0,
    "%fusion.11 = bf16[128,56,56,256]{3,0,2,1} fusion(%img)": 20.0,
    "%fusion.12 = f32[64]{0} fusion(%g2)": 0.125,
    "%fusion.13 = bf16[8192,2048]{1,0} fusion(%r, %s)": 1.25,
    "%copy-done.4 = f32[2048]{0} copy-done(%copy-start.4)": 0.0625,
    "%fusion.77 = f32[4]{0} fusion(%unknown)": 0.375,  # the record has no such op
    "%all-reduce.3 = f32[4]{0} all-reduce(%k)": 2.0,    # nor this one: a collective
}
WANT = {
    "attention_proj": 4.0 + 1.0 + 0.5,
    "attention_kernels": 5.0 + 3.0,
    "scan_kernels": 1.5,
    "ssm_mixer": 2.5,
    "expert_products": 4.0,
    # the outer loop's own 9 - (4 + 2 + 1), the gather, the inner loop's own
    # 1 - 0.75 and what it holds
    "expert_dispatch": 2.0 + 2.0 + 0.25 + 0.75,
    "head_loss": 0.5 + 7.5,
    "optimizer": 6.0,
    "gossip_combine": 0.25,
    "mlp": 10.0,
    "conv": 20.0,
    "batch_norm": 0.125,
    "unscoped": 1.25 + 0.0625 + 0.375,
}


def _run(ops_ms=OPS_MS):
    compute = sum(WANT.values())
    return {"trace": {"ops_ms_per_step": dict(ops_ms), "compute_ms_per_step": compute}}


@pytest.fixture
def library(monkeypatch):
    """Stands `programs` in the library's record's place; counts the reads."""
    state = {"programs": [STEP], "reads": 0}

    def read():
        state["reads"] += 1
        return state["programs"]

    monkeypatch.setattr(timeline, "step_scopes", read)
    return state


def test_an_ops_name_is_the_start_of_its_hlo_text():
    name = step_scopes.instruction_name
    assert name("%fusion.14 = (f32[256]{0:T(256)}, f32[256]) fusion(%a, %b), kind=k") \
        == "fusion.14"
    assert name("%while.7 = (s32[]{:T(128)}, f32[2048,12544]{1,0:T(8,128)}) wh") == "while.7"
    assert name("%a_name_cut_before_its_equals_sign") == "a_name_cut_before_its_equals_sign"


def test_every_op_is_in_one_group_and_a_loop_keeps_only_its_own_time(library, capsys):
    done = step_scopes.partition(_run())
    assert done["groups"] == pytest.approx(WANT)
    assert set(done["groups"]) <= {g for g, _, _ in step_scopes.RULES} | {"unscoped"}
    # each timed op once, with the time that is its own
    timed = [op.name for op, _, _ in done["ops"]]
    assert len(timed) == len(set(timed)) == 21
    own = {op.name: ms for op, _, ms in done["ops"]}
    assert own["while.27"] == 2.0 and own["while.28"] == 0.25 and own["while.31"] == 0.5
    assert "collective-permute-start.1" not in own and "fusion.99" not in own
    # the groups are the leaves' total: every traced compute op, no time twice
    leaves = sum(ms for name, ms in OPS_MS.items()
                 if not name.startswith(("%while", "%collective", "%all-reduce")))
    assert sum(done["groups"].values()) == pytest.approx(leaves + 2.0 + 0.25 + 0.5)
    assert done["found"] == pytest.approx(sum(WANT.values()) - 0.375)
    assert done["strays"] == [("fusion.77", 0.375)] and done["module"] == "jit_local_step"
    assert done["recomputed"] == pytest.approx(1.5 + 2.5)  # a kernel run again counts
    line = capsys.readouterr().out
    assert line.startswith("chipbench: step scopes: 1 program(s) read in ")
    assert "1 traced op(s) not in the record (0.375 ms)" in line and line.count("\n") == 1


def test_the_rules_are_read_in_order_and_a_component_is_matched_whole():
    group = step_scopes.group_of
    # a kernel's name comes before the scope it was called in
    assert group(_op("attention_global.12", FWD + "layers_3/attention_global/x")) \
        == "attention_kernels"
    assert group(_op("attention_global", "")) == "attention_kernels"
    assert group(_op("attention_globally.1", "")) == "unscoped"
    assert group(_op("ragged-dot-metadata.3", FWD + "moe_experts/while/body/x")) \
        == "expert_products"
    # `o` is a module, not a letter of another name; `Conv_12` a convolution
    assert group(_op("fusion.1", FWD + "layers_0/ffn_norm/mul")) == "mlp"
    assert group(_op("fusion.1", FWD + "layers_0/o/dot_general")) == "attention_proj"
    assert group(_op("fusion.1", FWD + "layers_0/norm/mul")) == "unscoped"
    assert group(_op("fusion.1", FWD + "Block_1/Conv_12/conv")) == "conv"
    assert group(_op("fusion.1", FWD + "Block_1/MyConv_12/conv")) == "unscoped"
    assert group(_op("fusion.1", BWD + "Block_1/BatchNorm_3/mul")) == "batch_norm"
    # the optimizer's scope before the model's: AdamW's fusions name no layer,
    # and one that did would still be the optimizer's
    assert group(_op("fusion.1", "jit(local_step)/optimizer_update/q/add")) == "optimizer"
    assert group(_op("fusion.1", "jit(local_step)/gradient_allreduce/div")) \
        == "gossip_combine"
    assert [g for g, _, _ in step_scopes.RULES][:3] == [
        "attention_kernels", "scan_kernels", "expert_products"]


def test_the_program_that_covers_the_most_of_the_trace_is_the_one_traced(library):
    other = StepProgram("jit_local_step", (
        _op("fusion.1", "jit(local_step)/gradient_allreduce/div"),
        _op("fusion.500", "jit(local_step)/optimizer_update/add"),
        _op("all-reduce.3", "jit(local_step)/gradient_allreduce/psum"),
    ))
    library["programs"] = [other, STEP, other]
    done = step_scopes.partition(_run())
    assert done["groups"] == pytest.approx(WANT)
    program, split = step_scopes.traced_program([other], {"fusion.1": 4.0, "fusion.2": 1.0})
    assert program is other and split["groups"] == {"gossip_combine": 4.0}
    assert step_scopes.traced_program([], {"fusion.1": 4.0}) == (None, None)
    library["programs"] = []
    assert step_scopes.partition(_run()) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_without_a_device_trace_reads_none_and_asks_for_nothing(
        name, monkeypatch):
    def asked():
        raise AssertionError("the library was asked for its record")

    monkeypatch.setattr(timeline, "step_scopes", asked)
    read = manifest.load_module(
        os.path.join(REPO, "chipbench", "layer_metrics", name + ".py")).read
    assert read({"trace": None}) is None                 # tracing off
    assert read({"trace": {"ops_ms_per_step": {}}}) is None    # a CPU rehearsal
    assert read({"trace": {"compute_ms_per_step": None}}) is None


def test_the_readers_split_one_trace_once_and_agree_with_the_partition(library):
    run = _run()
    got = {name: manifest.load_module(os.path.join(
        REPO, "chipbench", "layer_metrics", name + ".py")).read(run) for name in NEW}
    assert library["reads"] == 1
    assert all(v is not None and math.isfinite(v) for v in got.values())
    assert got.pop("recompute_ms_per_step") == pytest.approx(4.0)
    assert {n[:-len("_ms_per_step")]: v for n, v in got.items()} == pytest.approx(
        {g: WANT[g] for g in ("optimizer", "gossip_combine", "head_loss", "mlp",
                              "attention_proj", "expert_dispatch", "ssm_mixer", "conv",
                              "batch_norm", "unscoped")})
    # with the kernels' groups the new metrics are the whole of the step's compute
    kernels = sum(WANT[g] for g in ("attention_kernels", "scan_kernels", "expert_products"))
    assert sum(got.values()) + kernels == pytest.approx(
        run["trace"]["compute_ms_per_step"])


def test_a_group_with_no_op_in_the_trace_and_a_program_with_no_record_read_none(
        library, monkeypatch):
    few = {k: v for k, v in OPS_MS.items() if k.startswith(("%fusion.8", "%fusion.11"))}
    run = _run(few)
    assert step_scopes.group_ms(run, "optimizer") == 6.0
    assert step_scopes.group_ms(run, "ssm_mixer") is None
    assert step_scopes.recomputed_ms(run) is None
    monkeypatch.delattr(timeline, "step_scopes")  # a program from before the record
    for name in NEW:
        read = manifest.load_module(
            os.path.join(REPO, "chipbench", "layer_metrics", name + ".py")).read
        assert read(_run()) is None


def test_the_eleven_entries_of_the_manifest():
    per_layer = manifest.load_manifest()["per_layer"]
    by_name = {p["name"]: p for p in per_layer}
    assert [p["name"] for p in per_layer[-len(NEW):]] == [
        "optimizer_ms_per_step", "gossip_combine_ms_per_step", "head_loss_ms_per_step",
        "mlp_ms_per_step", "attention_proj_ms_per_step", "expert_dispatch_ms_per_step",
        "ssm_mixer_ms_per_step", "recompute_ms_per_step", "conv_ms_per_step",
        "batch_norm_ms_per_step", "unscoped_ms_per_step"]
    layers = {p["layer"] for p in per_layer[:-len(NEW)]}
    for name, (layer, cells) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": "ms", "better": "lower", "source": "device_trace",
            "layer": layer, "moves": "train_samples_s_chip", "workloads": cells}
        assert layer in layers  # a layer the benchmark already names
        assert not any("smallthinker" in cell for cell in cells)
        for cell in cells:
            assert name in {p["name"] for p in manifest.resolve(cell).per_layer}
