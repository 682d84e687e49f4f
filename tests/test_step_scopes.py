"""The program's record of its compiled step (``bluefog_tpu.timeline.step_scopes``):
which instructions it holds, what it says of each, when it is made, and that a
step after the first pays nothing for it."""

import functools
import re
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import models
from bluefog_tpu import timeline as tl
from bluefog_tpu import topology_util as tu
from bluefog_tpu import training
from bluefog_tpu.core import basics
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.hybrid import HybridMambaLM
from bluefog_tpu.models.resnet import BottleneckBlock
from bluefog_tpu.models.transformer import MixedAttentionMoELM
from bluefog_tpu.training import (make_decentralized_train_step, make_lm_loss_fns,
                                  replicate_for_mesh)

RANKS = 2
SEQ = 16


@pytest.fixture(autouse=True)
def fresh_context(devices):
    bf.init(devices=devices[:RANKS])
    bf.set_topology(tu.ExponentialTwoGraph(RANKS))
    tl._step_programs.clear()
    yield
    tl._step_programs.clear()
    bf.shutdown()


def _flash(**kw):
    return functools.partial(flash_attention, causal=True, block_q=8, block_k=8, **kw)


def _hybrid():
    model = HybridMambaLM(
        vocab_size=64, hidden_size=32, layer_kinds=("mamba", "attention"), dff=48,
        num_heads=4, num_kv_heads=2, head_dim=8, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, chunk=8, remat=True, head_chunks=2, attention_fn=_flash())
    return model, *make_lm_loss_fns(model), optax.adamw(1e-3)


def _mixed():
    model = MixedAttentionMoELM(
        vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        layer_windows=(8, None), num_experts=4, top_k=2, experts_held=(0, 1),
        expert_dff=16, head_chunks=2, attention_fn=_flash())
    return model, *make_lm_loss_fns(model), optax.adamw(1e-3)


def _resnet():
    model = models.ResNet(stage_sizes=[1, 1], block_cls=BottleneckBlock,
                          num_classes=10, num_filters=8)
    return model, model.apply, training.softmax_cross_entropy, optax.sgd(0.1, momentum=0.9)


BUILDERS = {"hybrid": _hybrid, "mixed": _mixed, "resnet": _resnet}


def _step(kind):
    """(step_fn, the arguments of a call) of a tiny model of `kind` through
    the library's ATC step on two ranks."""
    model, apply_fn, loss_fn, opt = BUILDERS[kind]()
    ctx = basics.context()
    if kind == "resnet":
        x = jnp.ones((RANKS, 2, 16, 16, 3), jnp.float32)
        y = jnp.zeros((RANKS, 2), jnp.int32)
        v = model.init(jax.random.PRNGKey(0), x[0], train=False)
        stats = replicate_for_mesh(v["batch_stats"], RANKS)
        apply_fn = functools.partial(model.apply, train=True)
    else:
        x = y = jnp.tile(jnp.arange(SEQ, dtype=jnp.int32) % 7, (RANKS, 1, 1))
        v, stats = model.init(jax.random.PRNGKey(0), x[0]), {}
    params = replicate_for_mesh(v["params"], RANKS)
    init_fn, step_fn = make_decentralized_train_step(
        apply_fn, opt, ctx.mesh, plan=ctx.plan, loss_fn=loss_fn,
        has_batch_stats=kind == "resnet", donate=False)
    return step_fn, (params, stats, init_fn(params), x, y)


@pytest.fixture(params=sorted(BUILDERS))
def stepped(request):
    """(kind, the one record of a step of that kind, its compiled text)."""
    step_fn, args = _step(request.param)
    step_fn(*args)
    (record,) = tl.step_scopes()
    (entry,) = tl._step_programs
    return request.param, record, entry[0].lower(*entry[1]).compile().as_text()


def _computation(text, name):
    """The instruction names of one computation of a module's text, read
    apart from the library's reader: the lines between its header and `}`."""
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines)
                 if re.match(r"(ENTRY )?%?" + re.escape(name) + r" \(", l))
    names = []
    for line in lines[start + 1:]:
        if line.startswith("}"):
            break
        names.append(re.match(r"\s+(?:ROOT )?%?(\S+) = ", line).group(1))
    return names


def test_every_instruction_of_the_entry_and_of_each_loop_is_in_the_record(stepped):
    kind, record, text = stepped
    assert record.module == "jit_local_step"
    by_name = {op.name: op for op in record.ops}
    assert len(by_name) == len(record.ops)
    entry = re.search(r"^ENTRY %?(\S+) \(", text, re.M).group(1)
    for name in _computation(text, entry):
        assert by_name[name].within is None
    loops = re.findall(
        r"^\s+(?:ROOT )?%?(\S+) = .*? while\(.*?condition=%?([^\s,]+), body=%?([^\s,]+)",
        text, re.M)
    assert loops if kind != "resnet" else not loops
    for loop, condition, body in loops:
        if loop in by_name:  # a loop inside a fusion's computation is not executed alone
            for name in _computation(text, body) + _computation(text, condition):
                assert by_name[name].within == loop
    # a fusion is one op to the device: its inside is not in the record
    fused = re.search(r"calls=%?(fused_computation[^\s,)]*)", text).group(1)
    inside = [n for n in _computation(text, fused) if not n.startswith("param")]
    assert inside and not set(inside) & set(by_name)


FOUND = {
    "hybrid": ("forward_backward", "optimizer_update", "lm_head_loss", "mlp_dense",
               "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm", "ssm_out_proj",
               "attention_global", "in_proj", "mixer_norm", "q", "o", "final_norm"),
    "mixed": ("forward_backward", "optimizer_update", "lm_head_loss", "moe_route",
              "moe_experts", "attention_window", "attention_global",
              "attention_rotary", "attn_norm", "ffn_norm", "q", "k", "v", "o"),
    "resnet": ("forward_backward", "optimizer_update", "gossip_combine", "conv_init",
               "bn_init", "Conv_0", "BatchNorm_0", "BottleneckBlock_0"),
}


def test_the_scopes_and_the_module_names_are_in_the_paths(stepped):
    kind, record, _ = stepped
    parts = {p for op in record.ops for p in op.path.split("/")}
    assert set(FOUND[kind]) <= parts, set(FOUND[kind]) - parts
    # a scope is opened once: no path names the optimizer twice
    assert not any(op.path.count("optimizer_update") > 1 for op in record.ops)


def test_backward_and_recomputed_are_read_off_the_path(stepped):
    """The markers as this JAX writes them: the gradient's ops are traced
    under `transpose(jvp(<model>))`, a remat block's second forward pass under
    `checkpoint/rematted_computation` inside it."""
    kind, record, _ = stepped
    assert (tl.BACKWARD_MARK, tl.RECOMPUTED_MARK) == ("transpose(", "rematted_computation")
    for op in record.ops:
        assert op.backward == ("transpose(" in op.path)
        assert op.recomputed == ("rematted_computation" in op.path)
    model = {"hybrid": "HybridMambaLM", "mixed": "MixedAttentionMoELM",
             "resnet": "ResNet"}[kind]
    backward = [op for op in record.ops if op.backward]
    assert any(f"transpose(jvp({model}))" in op.path for op in backward)
    assert all("transpose(jvp(" in op.path for op in backward)  # the loss's own too
    forward = [op for op in record.ops if f"/jvp({model})/" in op.path and not op.backward]
    assert forward and not any(op.recomputed for op in forward)
    assert not any(op.backward for op in record.ops if "optimizer_update" in op.path)
    again = [op for op in record.ops if op.recomputed]
    if kind == "hybrid":  # every block under nn.remat: the mixer's products run again
        assert all(op.backward for op in again)
        assert any("ssm_in_proj" in op.path for op in again)
        assert any("/checkpoint/rematted_computation/layer_0/" in op.path for op in again)
    elif kind == "resnet":
        assert not again
    else:  # no block is rematerialised, and the chunked loss runs no loop backward
        assert not again


def test_the_head_and_loss_is_three_products_in_the_forward_loop(stepped):
    """A decoder's step: the chunk's logits, `dx` and `dW` while the logits
    are there, and no product of the head's scope traced backward or made
    again."""
    kind, record, text = stepped
    products = re.findall(r' dot\(.*op_name="([^"]*lm_head_loss[^"]*)"', text)
    assert len(products) == (0 if kind == "resnet" else 3), products
    assert not any(tl.BACKWARD_MARK in p or tl.RECOMPUTED_MARK in p for p in products)
    assert not any(op.recomputed for op in record.ops if "lm_head_loss" in op.path)


def test_the_record_is_made_when_first_read_and_once(monkeypatch):
    parsed = []
    real = tl._parse_step_program
    monkeypatch.setattr(tl, "_parse_step_program",
                        lambda text: parsed.append(len(text)) or real(text))
    step_fn, args = _step("resnet")
    step_fn(*args)
    step_fn(*args)
    assert len(tl._step_programs) == 1 and not parsed  # noted, and nothing read
    first = tl.step_scopes()
    assert len(parsed) == 1
    assert tl.step_scopes()[0] is first[0] and len(parsed) == 1


def test_a_step_after_the_first_runs_nothing_of_the_record(monkeypatch):
    """With no profiler session and BLUEFOG_TIMELINE unset: the program is
    noted in the branch that builds it, and a later call runs of
    `timeline.py` only what a span that records nothing runs."""
    monkeypatch.delenv("BLUEFOG_TIMELINE", raising=False)
    noted = []
    real = training._register_step_program
    monkeypatch.setattr(training, "_register_step_program",
                        lambda *a: noted.append(1) or real(*a))
    step_fn, args = _step("resnet")
    step_fn(*args)
    assert len(noted) == 1
    ran = set()

    def profile(frame, event, arg):
        if event == "call" and frame.f_code.co_filename == tl.__file__:
            ran.add(frame.f_code.co_name)

    sys.setprofile(profile)
    try:
        step_fn(*args)
    finally:
        sys.setprofile(None)
    assert len(noted) == 1 and len(tl._step_programs) == 1
    assert ran <= {"__init__", "__enter__", "__exit__", "_session_edge"}, ran
    # another step is another program, noted once as well
    again, args = _step("resnet")
    again(*args)
    again(*args)
    assert len(noted) == 2 and len(tl._step_programs) == 2


def test_a_step_traced_into_another_program_notes_none():
    """`jax.jit(step_fn)` (chipbench's `structure()`, the compile tests) runs
    no `jit_local_step` of its own."""
    step_fn, args = _step("resnet")
    jax.jit(step_fn).lower(*args)
    assert not tl._step_programs and tl.step_scopes() == []


def test_only_the_newest_programs_are_kept():
    for i in range(tl.STEP_PROGRAMS + 3):
        tl._register_step_program(i, (jnp.zeros(()),))
    assert [e[0] for e in tl._step_programs] == list(range(3, tl.STEP_PROGRAMS + 3))


MODULE = """HloModule jit_local_step, is_scheduled=true, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %inside.1 = f32[4]{0} negate(%param_0)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%inner_body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%p), metadata={op_name="jit(local_step)/forward_backward/transpose(jvp(M))/x/while/body/mul"}
}

%inner_cond (p.1: (s32[], f32[4])) -> pred[] {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(false)
}

%body (q: (s32[], f32[4])) -> (s32[], f32[4]) {
  %q = (s32[], f32[4]{0:T(4)S(1)}) parameter(0)
  %while.2 = (s32[], /*index=1*/f32[4]{0}) while(%q), condition=%inner_cond, body=%inner_body, metadata={op_name="jit(local_step)/lm_head_loss/while"}
  ROOT %fusion.3 = (s32[], f32[4]{0}) fusion(%while.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(local_step)/forward_backward/transpose(jvp(M))/checkpoint/rematted_computation/layers_0/mlp_dense/up/dot_general" stack_frame_id=3}
}

%cond (q.1: (s32[], f32[4])) -> pred[] {
  %q.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.2 = pred[] constant(true)
}

%branch_a (x.1: f32[4]) -> f32[4] {
  ROOT %x.1 = f32[4]{0} parameter(0)
}

%branch_b (x.2: f32[4]) -> f32[4] {
  %x.2 = f32[4]{0} parameter(0)
  ROOT %neg.7 = f32[4]{0} negate(%x.2), metadata={op_name="jit(local_step)/optimizer_update/neg"}
}

%called (x.3: f32[4]) -> f32[] {
  %x.3 = f32[4]{0} parameter(0)
  %zero = f32[] constant(0)
  ROOT %reduce.4 = f32[] reduce(%x.3, %zero), dimensions={0}, to_apply=%sum
}

ENTRY %main.1 (arg: f32[4]) -> f32[4] {
  %arg = f32[4]{0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %while.1 = (s32[], f32[4]{0:T(4)S(1)}) while(%arg), condition=%cond, body=%body, metadata={op_name="jit(local_step)/lm_head_loss/while"}
  %conditional.5 = f32[4]{0} conditional(%arg, %arg, %arg), branch_computations={%branch_a, %branch_b}
  %call.6 = f32[] call(%arg), to_apply=%called
  ROOT %fusion.8 = f32[4]{0} fusion(%arg), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(local_step)/forward_backward/jvp(M)/layers_0/q/dot_general"}
}
"""


def test_the_reader_of_a_modules_text_on_every_kind_of_holder():
    record = tl._parse_step_program(MODULE)
    assert record.module == "jit_local_step"
    got = {op.name: op for op in record.ops}
    within = {name: op.within for name, op in got.items()}
    assert within == {
        "arg": None, "while.1": None, "conditional.5": None, "call.6": None,
        "fusion.8": None,
        "q": "while.1", "while.2": "while.1", "fusion.3": "while.1",
        "q.1": "while.1", "lt.2": "while.1",
        "p": "while.2", "tuple.2": "while.2", "p.1": "while.2", "lt.1": "while.2",
        "x.1": "conditional.5", "x.2": "conditional.5", "neg.7": "conditional.5",
        "x.3": "call.6", "zero": "call.6", "reduce.4": "call.6",
    }  # not a fusion's inside, not a reduction's scalar function
    assert got["fusion.3"] == tl.ScopedOp(
        "fusion.3", "jit(local_step)/forward_backward/transpose(jvp(M))/checkpoint/"
        "rematted_computation/layers_0/mlp_dense/up/dot_general", "while.1", True, True)
    assert got["tuple.2"].backward and not got["tuple.2"].recomputed
    assert got["fusion.8"][2:] == (None, False, False)
    assert got["arg"].path == "params[\\'w\\']" and got["lt.2"].path == ""
