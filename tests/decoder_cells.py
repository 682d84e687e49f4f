"""What the decoder configurations' tests share, and `TABLE`: one entry a
configuration with what it brings to the cases of `tests/test_decoder_cells.py`
(its cell, its stated cut, the fields that make it another model, its gauges,
hand counts, readers and seeds).  A new decoder configuration is a new entry.
Not collected; beside `tests/kda_oracle.py`."""

import dataclasses
import functools
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models import hybrid
from bluefog_tpu.models.transformer import _rotary, rotary_frequencies
from bluefog_tpu.parallel.expert import held_topk_experts, route_topk
from bluefog_tpu.telemetry import registry as telemetry
from bluefog_tpu.training import make_lm_loss_fns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import manifest, runner, seeded, step_scopes, trace_reduce  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OPTIMIZER = {"name": "adamw", "learning_rate": 3e-4, "weight_decay": 0.1}

# What float32 on both sides leaves between a program and its reference on the
# rehearsal's three to five layers is the order of sums (and, on Ling's, the
# chunked form's triangle against the recurrence's substitution): the widest
# leaf reads under 3e-5 on every configuration.  With the products in bfloat16
# the same comparison reads 5e-3 or more, so 2e-4 passes the one and fails the
# other with an order of magnitude on each side.
FLOAT32_GAP = 2e-4


# ---- the model against the plain reference ------------------------------------


def _float32_model(cell, sizes, **changed):
    """The program's model at `sizes`, computing in float32 so that the
    comparison with the float32 reference is of the mathematics."""
    return cell.module("program").build(sizes)["model"].clone(
        dtype=jnp.float32, **changed)


def _loss_and_grads(model, params, x, y):
    apply_fn = make_lm_loss_fns(model)[0]
    return jax.jit(jax.value_and_grad(
        lambda p: apply_fn({"params": seeded.nest(p)}, x, labels=y)))(params)


def reference_case(cell, sizes, widen=lambda params: params):
    """`(sizes, params, x, y, loss, grads)`: seed 11's weights (through
    `widen`) and one batch, and the plain reference's loss and gradients."""
    ref = cell.module("reference")
    params = widen(seeded.make_weights(ref, sizes, seed=11)[0])
    (x, y), = seeded.make_batches(ref, sizes, 11, ranks=1, pool=1)
    x, y = x[0], y[0]
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, {}, x, y, sizes), has_aux=True))(params)
    return sizes, params, x, y, float(loss), grads


def _worst_gap(got, want):
    """The widest relative L2 of a leaf's gradient from the reference's; a
    leaf that no gradient reaches on the reference's side has to be reached
    by none on the other."""
    gaps = {}
    for path in want:
        a, b = np.asarray(got[path], np.float64), np.asarray(want[path], np.float64)
        gaps["/".join(path)] = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def model_matches(cell, case, gap):
    """The float32 program's loss, leaves and every gradient against `case`'s,
    the reference's; returns the program's gradients."""
    sizes, params, x, y, loss, grads = case
    lp, gp = _loss_and_grads(_float32_model(cell, sizes), params, x, y)
    assert abs(float(lp) - loss) < 1e-5
    assert set(gp) == set(grads) == set(cell.module("reference").param_shapes(sizes)[0])
    worst, where = _worst_gap(gp, grads)
    assert worst < gap, (where, worst)
    return gp


def widened(params):
    """std 0.02 at hidden 64 leaves the experts' and a gate's terms at 1e-4 of
    the stream: widen them so that a wrong expert or gate shows in the loss."""
    return {p: a * (12.0 if p[-1] in ("wg", "wu", "wd", "router")
                    or p[-2] == "gate" else 1.0) for p, a in params.items()}


def leaf_shapes(model, ids):
    tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    return {p: a.shape for p, a in seeded.flatten(tree).items()}


def gauges_of(model, tokens, monkeypatch, tmp_path):
    """The gauges a model sets while its parameters' shapes are worked out."""
    monkeypatch.setenv("BFTPU_TELEMETRY", str(tmp_path))
    telemetry.reset()
    try:
        jax.eval_shape(lambda i: model.init(jax.random.PRNGKey(0), i),
                       jax.ShapeDtypeStruct((1, tokens), jnp.int32))
        return {g["name"]: g["value"] for g in telemetry.get_registry().snapshot()["gauges"]}
    finally:
        telemetry.reset()


# ---- a kernel, a mixer, a router, an expert layer against the references' ---------------


def values_and_grads(fn, args, weight):
    """`fn(*args)` and the gradient in every argument of its sum against `weight`."""
    def loss(*a):
        out = fn(*a)
        return jnp.sum(out * weight), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, tuple(range(len(args))), has_aux=True))(*args)
    return (out,) + grads


def latent_mixer(sizes, **fields):
    return hybrid.LatentAttentionMixer(
        sizes["num_attention_heads"], sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
        sizes["qk_rope_head_dim"], sizes["v_head_dim"],
        rotary_frequencies(sizes["qk_rope_head_dim"], sizes["rope_theta"]),
        sizes["rms_norm_eps"], jnp.float32,
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16),
        **fields)


def mixer_case(entry, **sizes):
    """One mixer of the rehearsal's sizes (with `sizes` over them), its
    reference's leaves seeded, a normed input and a cotangent."""
    sizes = dict(entry.cell.sizes(rehearse=True), num_hidden_layers=1, **sizes)
    params = seeded.make_weights(entry.reference, sizes, seed=5)[0]
    leaves = {p[2:]: v for p, v in params.items() if p[:2] == ("layer_0", "mixer")}
    r = jax.random.split(jax.random.PRNGKey(6), 2)
    u = jax.random.normal(r[0], (2, sizes["seq_len"], sizes["hidden_size"]))
    return sizes, leaves, u, jax.random.normal(r[1], u.shape)


def mixer_is_the_references(module, ref_fn, leaves, u, weight, sizes):
    """The value and every gradient of a mixer against the reference's, which
    takes one sequence at a time."""
    def program(p, u_):
        return jnp.sum(module.apply({"params": seeded.nest(p)}, u_) * weight)

    def reference(p, u_):
        full = {("layer_0", "mixer") + path: v for path, v in p.items()}
        out = jax.vmap(lambda one: ref_fn(one, full, ("layer_0", "mixer"), sizes, False))(u_)
        return jnp.sum(out * weight)

    got = jax.jit(jax.value_and_grad(program, (0, 1)))(leaves, u)
    want = jax.jit(jax.value_and_grad(reference, (0, 1)))(leaves, u)
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * max(1.0, abs(float(want[0])))
    for a, b in zip(jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])):
        gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)
        assert gap < 1e-4, gap


def router_case(tokens=96, d=24, experts=16, seed=0):
    r = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(r[0], (tokens, d))
    router = jax.random.normal(r[1], (d, experts)) * d ** -0.5
    return x, router, 0.3 * jax.random.normal(r[2], (experts,))


def routed_by_hand(x, router, bias, top_k, scale, groups=1, kept=1):
    """Sigmoid scores, the bias in the choice only, a group's score its two
    largest, the weights without the bias: a token and a group at a time, in
    float64."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64) @ np.asarray(router, np.float64)))
    per = s.shape[1] // groups
    chosen, weights = [], []
    for row in s:
        biased = row + np.asarray(bias, np.float64)
        score = [np.sort(biased[g * per:(g + 1) * per])[-2:].sum() for g in range(groups)]
        stay = np.argsort(score)[-kept:]
        allowed = [e for e in range(len(row)) if e // per in stay]
        picked = sorted(allowed, key=lambda e: -biased[e])[:top_k]
        chosen.append(picked)
        weights.append(scale * row[picked] / row[picked].sum())
    return np.array(chosen), np.array(weights)


def router_is_the_loop(ref, sizes, case, groups=1, kept=None):
    """The library's `route_topk`, the reference's `route` and the loop choose
    the same experts and weigh them alike; the bias moved the choice for some
    token, and never the weights."""
    x, router, bias = case
    top_k, scale = sizes["num_experts_per_tok"], sizes["routed_scaling_factor"]
    route = lambda **kw: route_topk(x, router, top_k, scale, score="sigmoid",
                                    groups=groups, groups_kept=kept, **kw)
    want_e, want_w = routed_by_hand(x, router, bias, top_k, scale, groups, kept or groups)
    p = {("b", "router"): router, ("b", "router_bias"): bias}
    for got_e, got_w in (route(bias=bias), ref.route(x, p, "b", sizes)):
        np.testing.assert_array_equal(np.asarray(got_e), want_e)
        np.testing.assert_allclose(got_w, want_w, rtol=2e-5)
        np.testing.assert_allclose(np.sum(got_w, -1), scale, rtol=1e-5)
    assert np.any(np.sort(np.asarray(route()[0]), -1) != np.sort(want_e, -1))


def no_gradient_reaches_the_bias(top_k, scale, **route):
    x, router, bias = router_case()

    def loss(r, b):
        _, w = route_topk(x, r, top_k, scale, score="sigmoid", bias=b, **route)
        return jnp.sum(w * jnp.arange(float(top_k)))
    dr, db = jax.grad(loss, (0, 1))(router, bias)
    assert float(jnp.max(jnp.abs(db))) == 0.0 and float(jnp.max(jnp.abs(dr))) > 0


def the_shares_add_up(ref, terms, sizes, experts, per, shared_width, **route):
    """`experts` experts over shares of `per`: the routed parts that the shares
    compute (`held_topk_experts`, each told its experts) plus the shared
    expert once are the uncut reference's layer (`terms` of it, every expert
    held), and every share adds.  Returns the input and the layer's leaves."""
    d, f = 24, 16
    top_k, scale = sizes["num_experts_per_tok"], sizes["routed_scaling_factor"]
    x, router, bias = router_case(d=d, experts=experts, seed=3)
    r = jax.random.split(jax.random.PRNGKey(9), 6)
    stack = lambda key, *shape: 0.3 * jax.random.normal(key, shape)
    p = {("b", "router"): router, ("b", "router_bias"): bias,
         ("b", "wg"): stack(r[0], experts, d, f), ("b", "wu"): stack(r[1], experts, d, f),
         ("b", "wd"): stack(r[2], experts, f, d),
         ("b", "shared", "wg"): stack(r[3], d, shared_width),
         ("b", "shared", "wu"): stack(r[4], d, shared_width),
         ("b", "shared", "wd"): stack(r[5], shared_width, d)}
    shared = ref.gated_mlp(x, p, ("b", "shared"), False)
    whole = terms(x, p, "b", sizes, False, tuple(range(experts))) + shared
    chosen, weights = route_topk(x, router, top_k, scale, score="sigmoid", bias=bias,
                                 **route)
    total = shared                                          # every share's alike: once
    for share in range(experts // per):
        held = tuple(range(share * per, (share + 1) * per))
        stacks = {n: p[("b", n)][share * per:(share + 1) * per] for n in ("wg", "wu", "wd")}
        part = held_topk_experts(x, chosen, weights, stacks, held, experts,
                                 activation=jax.nn.silu)
        one = terms(x, {**p, **{("b", n): stacks[n] for n in stacks}}, "b", sizes, False,
                    held)
        np.testing.assert_allclose(part, one, atol=2e-5)   # a share is the reference's
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part
    np.testing.assert_allclose(total, whole, atol=5e-5)
    return x, p


# ---- the cell's rehearsal ---------------------------------------------------------


def not_correct_under(cell, wrap_job, seed=2**31 + 99):
    """The runner with a broken job in the timed path's place: `correct` has
    to come out false by one of the cell's limits."""
    args = type("Args", (), dict(rehearse=True, seed=seed, seconds=0.5, trace=0))
    result = runner.run(args, 0.0, cell, wrap_job=wrap_job)
    assert result["correct"] is False
    over = [k for k, c in result["checks"].items()
            if c["limit"] and c["value"] is not None and c["value"] > c["limit"]]
    assert over, result["checks"]


class Unchanged:
    """A job in the timed path's place that hands its state back as it came."""

    def __init__(self, job):
        self.job = job
        self.start = jax.tree_util.tree_map(jnp.copy, job.state)

    def __getattr__(self, name):
        return getattr(self.job, name)

    def step(self, k):
        out = self.job.step(k)
        self.job.state = jax.tree_util.tree_map(jnp.copy, self.start)
        return out


def routing_row(tool, cell_name, capsys):
    """The row a routing tool prints for one seed's rehearsal, and its refusal
    to count where there is no TPU."""
    assert tool.main(["--workload", cell_name, "--seeds", "1", "--seconds", "0.5",
                      "--rehearse"]) == 0
    row, = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    assert row["seed"] == 300 and row["failed"] == 0 and row["steps_in_window"] >= 2
    assert tool.main(["--workload", cell_name, "--seeds", "1"]) == 2
    captured = capsys.readouterr()
    assert captured.out == "" and "no TPU" in captured.err
    return row


def rehearse_through_the_command_line(cell_name, seed):
    """The last line of `python -m chipbench --rehearse` in a child process."""
    out = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell_name, "--rehearse",
         "--seed", str(seed), "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---- the table ------------------------------------------------------------------------


@dataclasses.dataclass
class Decoder:
    """One decoder configuration: what differs from the next.  A field left at
    its default leaves the configuration out of the cases that read it."""

    cell_name: str
    catalog: str                    # the row of the guide's catalog: the source's config.json
    # no width differs from the source and the cut is stated
    cut: dict                       # the keys of the source's config.json that were cut
    reduced: list
    published_stated: dict          # under the file's `published`
    marks: dict                     # field of the file: what its text has to say
    mix_as: tuple                   # (a standing cell, the keys its mix may differ in)
    per_layer: set                  # the manifest's per-layer metrics that list the cell
    cut_also: tuple = ()            # under `cut` beyond `reduced`
    mix_sizes: dict = dataclasses.field(
        default_factory=lambda: {"per_rank_batch": 1, "seq_len": 8192})
    config_says: dict = dataclasses.field(default_factory=dict)
    sizes_say: dict = dataclasses.field(default_factory=dict)   # where `sizes` says another
    parameters: tuple = ()          # (what, pick(path), count) at the cell's sizes
    flops: object = None            # (flops, sizes) -> (what, got, want)
    readers: dict = dataclasses.field(default_factory=dict)  # id: cell -> (metric, run, want)
    # the model against the plain reference
    float32_gap: float = FLOAT32_GAP
    router_biases: int = 0          # leaves that no gradient reaches
    no_leaf_named: tuple = ()
    rules: dict = dataclasses.field(default_factory=dict)    # id: the fields changed
    remat_off: dict = None
    adamw_seed: int = None          # of three steps held to the cell's LIMITS
    decayed_only: tuple = ()        # leaves that AdamW's decay alone moves in them
    gauges: dict = dataclasses.field(default_factory=dict)   # id: sizes, fields, tokens, wanted
    gauges_absent: tuple = ()
    foreign_kinds: tuple = None     # (layer kinds, the name the error has to say)
    # the rehearsal
    control_seed: int = None
    unchanged_seed: int = None
    cli_seed: int = None

    @property
    def name(self):
        """The configuration's, as the manifest's cells are named after it."""
        return self.cell_name.split("-atc-")[0]

    @functools.cached_property
    def cell(self):
        return manifest.resolve(self.cell_name)

    @functools.cached_property
    def reference(self):
        return self.cell.module("reference")

    @functools.cached_property
    def seeded_case(self):
        """`reference_case` at the cell's rehearsal sizes, once a process."""
        return reference_case(self.cell, self.cell.sizes(rehearse=True))

    def published_config(self):
        """The source's config.json as the guide's catalog copies it; None
        where the guide is not installed."""
        if not os.path.exists(CATALOG):
            return None
        with open(CATALOG) as catalog:
            row = next(json.loads(line) for line in catalog
                       if f'"{self.catalog}"' in line)
        assert self.cell.config["source"] == row["source_url"]
        return row["config"]


def _layer(i, but=()):
    return lambda p: p[0] == f"layer_{i}" and p[1] not in but


def _mixer(i):
    return lambda p: p[:2] == (f"layer_{i}", "mixer")


_FFN = ("mixer", "mixer_norm", "mlp_norm")          # what a layer has beside these
_ENDS = lambda p: p[0] in ("embed", "head", "final_norm")
_ALL = lambda p: True
_PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
_SPLIT_BY_SCOPE = {  # PR 41: the step's split by scope
    "optimizer_ms_per_step", "head_loss_ms_per_step", "mlp_ms_per_step",
    "attention_proj_ms_per_step"}


def _traced(ops, flops, sizes, **more):
    """A run as the readers are handed it, from the traced ops' milliseconds."""
    return {"trace": {"ops_ms_per_step": ops, **more}, "peaks": _PEAKS,
            "flops_per_sample": flops.train_flops_per_sample(sizes)}


def _silent(metrics, *empties):
    """Runs that have nothing for these readers: each reads None, no raise."""
    for empty in ({"trace": None}, *empties):
        for metric in metrics:
            yield metric, empty, None


# ---- smallthinker-21b-a3b ---------------------------------------------------------------


def _smallthinker_flops(flops, sizes):
    yield "the pairs", flops.visible_pairs(8192), 33_558_528
    yield "the pairs in a window", flops.visible_pairs(8192, 4096), 25_167_872
    yield "a window past the sequence", flops.visible_pairs(8192, 9000), \
        flops.visible_pairs(8192)
    yield "a token", 2 * flops.forward_macs(sizes) / sizes["seq_len"], \
        pytest.approx(492.57e6, rel=1e-4)
    yield "the head", 2 * 2560 * 18992, pytest.approx(97.2e6, rel=1e-3)
    yield "a step of two", flops.train_flops_per_sample(sizes) * 2, \
        pytest.approx(24.21e12, rel=1e-3)
    # a kernel call: the dK/dV kernel does twice the forward's products
    f, fb = flops.kernel_call(sizes, "fwd", 4096)
    d, db = flops.kernel_call(sizes, "dkv", 4096)
    yield "dK/dV", d, 2 * f
    yield "the forward", f, 2 * 2 * 128 * 25_167_872 * 56
    yield "the bytes", 1.0e9 < fb < db < 1.6e9, True


SMALLTHINKER = Decoder(
    cell_name="smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip",
    catalog="SmallThinker-21BA3B-Instruct",
    cut={"num_hidden_layers": 4, "vocab_size": 18992},
    reduced=["num_hidden_layers", "moe_num_primary_experts_held", "vocab_size"],
    published_stated={"num_hidden_layers": 52, "moe_num_primary_experts": 64,
                      "vocab_size": 151936},
    config_says={"moe_num_primary_experts_held": 8,
                 "sliding_window_layout": [0, 1, 1, 1] * 13,
                 "rope_layout": [0, 1, 1, 1] * 13,
                 "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
                 "tie_word_embeddings": False},
    marks={"deployment": ("eight", "one period")},
    mix_sizes={"per_rank_batch": 2, "seq_len": 8192},
    mix_as=("laguna-xs.2-atc-warmup-b1-s8k-1chip", ("sizes", "describes")),
    per_layer={"train_step_host_ms_per_step", "attention_ms_per_step",
               "expert_ms_per_step", "flash_fwd_window_roofline",
               "flash_bwd_dkv_window_roofline", "flash_bwd_dq_window_roofline"},
    parameters=(("the cut", _ALL, 370_547_200),),
    flops=_smallthinker_flops,
)


# ---- laguna-xs.2 --------------------------------------------------------------------------


def _laguna_flops(flops, sizes):
    yield "the pairs", flops.visible_pairs(8192), 33_558_528
    yield "the pairs in a window", flops.visible_pairs(8192, 512), 4_063_488
    yield "the windows", flops.windows(sizes), [None, 512, 512, 512, None]
    d, s = 2048, 8192
    attn = lambda heads: d * heads * 128 * 2 + 2 * d * 1024 + d * heads  # q, o, k, v, gate
    sparse = d * 256 + 1 * 3 * d * 512 + 3 * d * 512  # router, 8 x 32 / 256 experts, shared
    macs = (s * (attn(48) + 3 * d * 8192)                      # layer 0
            + 3 * s * (attn(64) + sparse) + s * (attn(48) + sparse)
            + 2 * 2 * 33_558_528 * 48 * 128 + 3 * 2 * 4_063_488 * 64 * 128
            + s * d * 12544)
    yield "forward", flops.forward_macs(sizes), macs
    yield "a step", flops.train_flops_per_sample(sizes), 6 * macs
    yield "a step, about", 6 * macs, pytest.approx(19.705e12, rel=1e-4)
    pairs = 6 * (2 * 2 * 33_558_528 * 48 * 128 + 3 * 2 * 4_063_488 * 64 * 128)
    yield "the pairs' part", pairs, pytest.approx(6.147e12, rel=1e-3)  # 4.95 in the two full layers
    # a kernel call counts the head count of its layer's kind
    f, fb = flops.kernel_call(sizes, "fwd", 512)
    yield "a window layer's forward", f, 2 * 2 * 128 * 4_063_488 * 64
    yield "a full layer's forward", flops.kernel_call(sizes, "fwd", None)[0], \
        2 * 2 * 128 * 33_558_528 * 48
    yield "dK/dV", flops.kernel_call(sizes, "dkv", 512)[0], 2 * f
    yield "dQ", flops.kernel_call(sizes, "dq", 512)[0], 3 * f // 2
    # the blocks are the program's; at 512 x 512 a row block meets 2 key blocks
    # (1 the first): q and o once, k and v a tile, 64 heads
    bq, bk = flops.program_blocks()["sliding_attention"]
    tiles = sum(min(i * (bq // bk) + bq // bk, 8192 // bk)
                - max(i * bq - 511, 0) // bk for i in range(8192 // bq))
    yield "the forward's bytes", fb, 64 * (2 * (8192 // bq) * bq + 2 * tiles * bk) * 128 * 2
    # dK/dV writes the 8 shared heads once, not the 64
    yield "dK/dV's bytes", flops.kernel_call(sizes, "dkv", 512, (1024, 512))[1], \
        8 * 4 * 8192 * 256 + 64 * 2 * 23 * 1024 * 256


def _laguna_readers(cell):
    ops = {"%flash_fwd_window.3 = bf16[...]": 4.0, "%flash_fwd_window.4": 4.5,
           "%flash_bwd_dkv_window.1": 6.0, "%flash_bwd_dq_window.1": 5.0,
           "%attention_global.2": 9.0, "%attention_global.7": 12.5,
           "%fusion.9": 100.0, "%flash_fwd_windowed": 50.0}
    run = {"trace": {"ops_ms_per_step": ops}}
    yield "attention_window_ms_per_step", run, 19.5
    yield "attention_global_ms_per_step", run, 21.5
    yield "attention_ms_per_step", run, 19.5 + 21.5
    # a program without such kernels, and a run without a trace
    yield from _silent(("attention_window_ms_per_step", "attention_global_ms_per_step"),
                       {"trace": {"ops_ms_per_step": {"%fusion": 1.0}}})


def _beside_its_experts(i):
    """Layer `i` without its routed experts' stacks."""
    prefix = f"layer_{i}"
    return lambda p: (p[0] == prefix and p[-1] not in ("wg", "wu", "wd")
                      or p[:2] == (prefix, "shared") or p[:2] == (prefix, "mlp"))


LAGUNA = Decoder(
    cell_name="laguna-xs.2-atc-warmup-b1-s8k-1chip",
    catalog="Laguna-XS.2",
    cut={"num_hidden_layers": 5, "vocab_size": 12544},
    reduced=["num_hidden_layers", "num_experts_held", "vocab_size"],
    published_stated={"num_hidden_layers": 40, "num_experts": 256, "vocab_size": 100352},
    config_says={
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                        "sliding_attention"] * 10,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
        "gating": True, "tie_word_embeddings": False, "attention_bias": False,
        "moe_apply_router_weight_on_input": False, "num_experts_held": 32},
    marks={"deployment": ("eight", "one period", "leading dense layer"),
           "assumed": ("(i) the gate is a sigmoid", "(ii) softmax router", "(iii) SiLU",
                       "(iv) no gate on the shared expert",
                       "(v) the half-split rotary convention")},
    mix_as=("smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip", ("sizes", "describes")),
    per_layer=_SPLIT_BY_SCOPE | {
        "train_step_host_ms_per_step", "attention_ms_per_step", "expert_ms_per_step",
        "flash_fwd_window_roofline", "flash_bwd_dkv_window_roofline",
        "flash_bwd_dq_window_roofline", "attention_window_ms_per_step",
        "attention_global_ms_per_step", "expert_dispatch_ms_per_step",
        "unscoped_ms_per_step"},
    parameters=(("the dense layer, full attention", _beside_its_experts(0), 79_794_176),
                ("a window layer beside its experts", _beside_its_experts(1), 41_553_920),
                ("the full sparse layer beside its", _beside_its_experts(4), 33_132_544),
                ("the cut", _ALL, 691_623_936)),
    flops=_laguna_flops,
    readers={"by-kind": _laguna_readers},
    gauges={"a-router-that-chooses": dict(
        # 3 of 8 experts, 4 of them held, as the file's own cases have them
        sizes=dict(num_experts=8, num_experts_per_tok=3, num_experts_held=4, seq_len=32),
        fields={}, tokens=32, wanted={
            "attention.heads_window": 6, "attention.heads_global": 4,
            "attention.kv_heads": 2, "attention.rotary_dims_window": 16,
            "attention.rotary_dims_global": 8, "moe.shared_width": 32,
            "moe.routed_scale": 2.5, "moe.dense_layers": 1, "attention.window": 24,
            "moe.experts_held": 4})},
    control_seed=2**31 + 35,
)


# ---- granite-4.0-h-micro ------------------------------------------------------------------


def _rotated(q, k, v):
    positions = jnp.arange(q.shape[1])
    return flash_attention(_rotary(q, positions), _rotary(k, positions), v, causal=True)


def _granite_gauges(state, tokens, conv_kernel_layers):
    """The rehearsal's convolution is 128 + 2 x 32 channels wide: B and C are
    no whole 128-lane block and every layer takes the expression; with a state
    of 64 they are one, and with 12 tokens for 32 the tokens are no whole 8-row
    tiles."""
    return dict(sizes={}, fields={"ssm_state": state}, tokens=tokens, wanted={
        "ssm.layers": 2, "ssm.heads": 8, "ssm.head_dim": 16, "ssm.state": state,
        "ssm.groups": 1, "ssm.chunk": 16, "ssm.conv_width": 4,
        "ssm.conv_kernel_layers": conv_kernel_layers,
        "attention.layers_global": 1, "attention.heads_global": 4,
        "attention.kv_heads": 2, "attention.scale": 0.015625, "lm.tied_head": 1,
        "lm.remat_blocks": 3, "lm.remat_kept_names": 4,
        # bfloat16 of the tokens: an attention layer's [4, T, 16] and float32
        # [4, T], three layers' [T, 64] and [T, 192]
        "lm.remat_kept_mb": tokens * (128 + 16 + 384 + 1152) / 1e6})


def _granite_flops(flops, sizes):
    d, s, f = 2048, 8192, 8192
    visible = 256 * 257 // 2
    scan = 32 * (64 * (visible * 64 + 2 * 256 * 128 * 64) + visible * 128)
    yield "a layer's scan", flops.scan_macs(sizes), scan
    mamba = s * (d * 8512 + 4096 * d + 3 * d * f) + scan
    attention = s * (d * 3072 + 2048 * d + 3 * d * f) + 2 * 33_558_528 * 2048
    macs = 9 * mamba + attention + s * d * 12544
    yield "forward", flops.forward_macs(sizes), macs
    yield "a step", flops.train_flops_per_sample(sizes), 6 * macs
    yield "a step, about", 6 * macs, pytest.approx(39.5e12, rel=5e-3)
    yield "the scans' part", 6 * 9 * scan, pytest.approx(0.70e12, rel=1e-2)
    # a kernel call: the scan's products, twice them backward; x and y (and
    # theirs) at 2 bytes, the step sizes at 4, a float32 state a head a chunk
    ops, nbytes = flops.kernel_call(sizes, "fwd")
    yield "the forward kernel", (ops, nbytes), \
        (2 * scan, 2 * s * 4096 * 2 + 2 * s * 128 * 2 + s * 64 * 4)
    yield "the backward kernel", flops.kernel_call(sizes, "bwd"), \
        (2 * ops, 3 * s * 4096 * 2 + 4 * s * 128 * 2 + 2 * s * 64 * 4
         + 32 * 64 * 64 * 128 * 4)
    for kernel in ("fwd", "bwd"):  # bound by the bytes, as counted
        work, nbytes = flops.kernel_call(sizes, kernel)
        yield f"{kernel}: the bytes lead", nbytes / 819e9 > work / 197e12, True
    yield "forward calls a step", flops.kernel_calls_per_step(sizes, "fwd"), 18  # recomputed too
    yield "backward calls a step", flops.kernel_calls_per_step(sizes, "bwd"), 9


def _granite_readers(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    ops = {"%ssd_chunk_fwd.3 = (bf16[...]": 1.0, "%ssd_chunk_fwd.4": 1.5,
           "%ssd_chunk_bwd.1": 3.0, "%attention_global.2": 9.0,
           "%fusion.9": 100.0, "%ssd_chunk_fwd_other": 50.0}
    run = _traced(ops, flops, sizes)
    yield "ssm_scan_ms_per_step", run, 5.5
    yield "attention_global_ms_per_step", run, 9.0
    yield "attention_ms_per_step", run, 9.0
    for kernel, ms in (("fwd", 2.5), ("bwd", 3.0)):
        work, nbytes = flops.kernel_call(sizes, kernel)
        ideal = flops.kernel_calls_per_step(sizes, kernel) * max(
            work / 197e12, nbytes / 819e9)
        yield f"ssd_chunk_{kernel}_roofline", run, pytest.approx(100 * ideal / (ms / 1e3))
    # a program without such kernels, a run without a trace, a rehearsal and a
    # run of another cell
    yield from _silent(("ssd_chunk_fwd_roofline", "ssd_chunk_bwd_roofline"),
                       {"trace": {"ops_ms_per_step": {"%fusion": 1.0}}},
                       dict(run, peaks=None), dict(run, flops_per_sample=1.0))
    yield "ssm_scan_ms_per_step", {"trace": None}, None


GRANITE = Decoder(
    cell_name="granite-4.0-h-micro-atc-warmup-b1-s8k-1chip",
    catalog="granite-4.0-h-micro",
    cut={"num_hidden_layers": 10, "vocab_size": 12544},
    reduced=["num_hidden_layers", "vocab_size"],
    cut_also=("parameters",),
    published_stated={"num_hidden_layers": 40, "vocab_size": 100352},
    marks={"deployment": ("eight", "period"),
           "assumed": ("[z, xBC, dt]", "inverse softplus", "log-uniform in [0.001, 0.1]",
                       "A uniform in [1, 16]", "silu(z) first", "residual_multiplier",
                       "nope", "recomputed")},
    mix_as=("laguna-xs.2-atc-warmup-b1-s8k-1chip", ("describes",)),
    per_layer=_SPLIT_BY_SCOPE | {
        "train_step_host_ms_per_step", "attention_ms_per_step",
        "attention_global_ms_per_step", "ssm_scan_ms_per_step",
        "ssd_chunk_fwd_roofline", "ssd_chunk_bwd_roofline", "ssm_mixer_ms_per_step",
        "recompute_ms_per_step", "unscoped_ms_per_step"},
    # the parameters of the cut, as the issue's arithmetic has them
    parameters=(("a state-space layer", _layer(0), 76_182_976),
                ("the attention layer", _layer(5), 60_821_504),
                ("the cut", _ALL, 772_160_448)),
    flops=_granite_flops,
    readers={"scan": _granite_readers},
    # State-space, attention, state-space in float32 throughout: only the order
    # of sums differs, 1e-4 on every leaf
    float32_gap=1e-4,
    # a model with one of them changed is another model
    rules={"embedding_multiplier": dict(embedding_multiplier=1.0),
           "attention_multiplier": dict(attention_multiplier=None),
           "residual_multiplier": dict(residual_multiplier=1.0),
           "logits_scaling": dict(logits_scaling=1.0),
           "a_rotary": dict(attention_fn=_rotated)},
    remat_off=dict(remat=False),
    adamw_seed=2**31 + 7,
    gauges={"as-rehearsed": _granite_gauges(32, 32, 0),
            "shapes-that-tile": _granite_gauges(64, 32, 2),
            "tokens-that-do-not": _granite_gauges(64, 12, 0)},
    foreign_kinds=(("mamba", "linear_attention"), "linear_attention"),
    control_seed=2**31 + 35,
    unchanged_seed=2**31 + 99,
    cli_seed=2**31 + 5,
)


# ---- ling-3.0-flash-vl ---------------------------------------------------------------------


def _ling_gauges(heads, tokens, kernel_layers):
    """At the rehearsal's 4 heads of 16 a projection's 64 convolved channels
    take the expression; 8 heads tile, and 12 tokens for 32 do not."""
    return dict(sizes={}, fields=dict(num_heads=heads), tokens=tokens, wanted={
        "kda.layers": 3, "kda.heads": heads, "kda.head_dim": 16, "kda.chunk": 32,
        "kda.lower_bound": -5, "kda.kernel_layers": kernel_layers,
        "kda.intra_kernel_layers": 3, "mla.layers": 1, "mla.kv_rank": 32,
        "mla.qk_dims": 24, "mla.v_dims": 16, "attention.layers_global": 1,
        "attention.heads_global": heads, "moe.score": 1, "moe.groups": 4,
        "moe.groups_kept": 2, "moe.shared_width": 32, "moe.routed_scale": 2.5,
        "moe.dense_layers": 1, "moe.experts_held": 4, "moe.experts_total": 16,
        "moe.top_k": 4, "lm.tied_head": 0, "lm.remat_blocks": 4,
        "lm.remat_kept_names": 3,
        # bfloat16 of the tokens: the latent layer's [heads, T, 16] and
        # float32 [heads, T], three delta layers' [T, heads x 16]
        "lm.remat_kept_mb": tokens * heads * (32 + 4 + 3 * 32) / 1e6})


def _ling_flops(flops, sizes):
    d, s, inner = 2560, 8192, 4096
    vis = 64 * 65 // 2
    fwd_kernel = 3 * 64 * 128 * 128 + vis * 128
    delta = 128 * 32 * ((vis - 64) * 128 + 3 * vis * 128 + fwd_kernel)
    kda = s * (6 * d * inner + d * 32) + delta
    mla = (s * (d * 32 * 192 + d * 576 + 512 * 32 * 256 + d * 32 + inner * d)
           + (s * (s + 1) // 2) * 32 * (192 + 128))
    experts = s * (d * 512 + 8 * 8 / 512 * 3 * d * 768 + 3 * d * 768)
    want = s * d * 19648 + 6 * kda + mla + s * 3 * d * 6144 + 6 * experts
    yield "forward", flops.forward_macs(sizes), pytest.approx(want, rel=1e-12)
    yield "a step", flops.train_flops_per_sample(sizes), pytest.approx(6 * want, rel=1e-12)
    yield "the forward kernel", flops.kernel_macs(sizes, "fwd"), fwd_kernel
    yield "the backward kernel", flops.kernel_macs(sizes, "bwd"), \
        7 * 64 * 128 * 128 + 2 * vis * 128
    # the delta rule's chunk products are a twentieth of a layer's products
    yield "the chunk products' share", 0.03 < delta / kda < 0.08, True


def _ling_readers(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    op = lambda name, path, within=None: types.SimpleNamespace(
        name=name, path=path, within=within, recomputed=False)
    ops = {"%kda_chunk_fwd.3 = (bf16[...]": 4.0, "%kda_chunk_fwd.4": 6.0,
           "%kda_chunk_bwd.1": 9.0, "%attention_global.2": 7.0, "%fusion.9": 100.0,
           "%kda_chunk_fwd_other": 50.0, "%fusion.1": 2.0, "%fusion.2": 3.0,
           "%fusion.3": 5.0}
    root = "jit(local_step)/forward_backward/layer_2/mixer/"
    record = [(op("kda_chunk_fwd.3", root + "kda_chunk/while/body", "while.1"), "x", 4.0),
              (op("kda_chunk_fwd.4", root + "kda_chunk/while/body", "while.2"), "x", 6.0),
              (op("kda_chunk_bwd.1", root + "kda_chunk/while/body", "while.3"), "x", 9.0),
              (op("fusion.1", root + "kda_chunk/kda_intra/dot_general"), "x", 2.0),
              (op("fusion.2", root + "kda_gates/kda_f/dot_general"), "x", 3.0),
              (op("fusion.3", root + "mla_kv_up/dot_general"), "x", 5.0),
              (op("fusion.9", root + "o/dot_general"), "attention_proj", 100.0)]
    memo = lambda ops_: {step_scopes.MEMO: {
        "ops": ops_, "groups": {}, "recomputed": 0.0, "found": 0.0}}
    run = _traced(ops, flops, sizes, **memo(record))
    yield "kda_kernels_ms_per_step", run, 19.0
    yield "kda_mixer_ms_per_step", run, 5.0
    yield "latent_proj_ms_per_step", run, 5.0
    yield "attention_global_ms_per_step", run, 7.0
    trips = 32 // flops.HEADS_A_CALL
    for kernel, ms, sites in (("fwd", 10.0, 2), ("bwd", 9.0, 1)):
        work, nbytes = flops.kernel_call(sizes, kernel)
        ideal = sites * trips * max(work / 197e12, nbytes / 819e9)
        yield f"kda_chunk_{kernel}_roofline", run, pytest.approx(100 * ideal / (ms / 1e3))
    # a call outside a loop counts once
    outside = [(op("kda_chunk_fwd.3", root + "kda_chunk"), "x", 4.0)] + record[1:]
    work, nbytes = flops.kernel_call(sizes, "fwd")
    yield "kda_chunk_fwd_roofline", _traced(ops, flops, sizes, **memo(outside)), \
        pytest.approx(100 * (1 + trips) * max(work / 197e12, nbytes / 819e9) / 10e-3)
    # a program without such kernels or without a record of its step (the
    # parent's), a run without a trace, a rehearsal, a run of another cell
    bare = _traced({"%fusion": 1.0}, flops, sizes, **memo([]))
    yield from _silent(("kda_chunk_fwd_roofline", "kda_chunk_bwd_roofline"), bare,
                       dict(run, peaks=None), dict(run, flops_per_sample=1.0))
    yield from _silent(("kda_kernels_ms_per_step", "kda_mixer_ms_per_step",
                             "latent_proj_ms_per_step"), bare)


LING = Decoder(
    cell_name="ling-3.0-flash-vl-atc-warmup-b1-s8k-1chip",
    catalog="Ling-3.0-flash-VL",
    cut={"num_hidden_layers": 7, "num_experts": 8, "vocab_size": 19648},
    reduced=["num_hidden_layers", "num_experts", "vocab_size"],
    cut_also=("parameters",),
    published_stated={"num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184},
    sizes_say={"num_experts": 512, "num_experts_held": 8,
               "published_layer_index": [0, 2, 3, 4, 5, 6, 7]},
    marks={"deployment": ("64", "stage of six"), "expert_load": ("sixty-fourth",),
           "assumed": ("(i + 1) % layer_group_size", "no rotary in the KDA layers",
                       "reading not taken", "kda_safe_gate", "no_kda_lora", "half-split",
                       "multi-token prediction", "log-uniform in [0.001, 0.1]",
                       "uniform in [-0.05, 0.05]", "recomputed", "balancing update")},
    mix_as=("laguna-xs.2-atc-warmup-b1-s8k-1chip", ("describes",)),
    per_layer=_SPLIT_BY_SCOPE | {
        "train_step_host_ms_per_step", "attention_ms_per_step",
        "attention_global_ms_per_step", "expert_ms_per_step",
        "expert_dispatch_ms_per_step", "recompute_ms_per_step",
        # this configuration's own
        "kda_kernels_ms_per_step", "kda_chunk_fwd_roofline", "kda_chunk_bwd_roofline",
        "kda_mixer_ms_per_step", "latent_proj_ms_per_step"},
    parameters=(("a delta-rule mixer", _mixer(0), 63_049_888),
                ("the latent mixer", _mixer(4), 31_965_696),
                ("an expert layer", _layer(1, but=_FFN), 54_395_392),
                ("the dense layer", _layer(0), 110_240_928),
                ("a delta-rule layer with experts", _layer(1), 117_450_400),
                ("the latent layer", _layer(4), 86_366_208),
                ("the embedding, the head, the last norm", _ENDS, 100_600_320),
                ("the cut", _ALL, 884_459_456)),
    flops=_ling_flops,
    readers={"delta-rule": _ling_readers},
    router_biases=3,
    rules={"the_gates_bound": dict(lower_bound=-2.0),
           "routed_scale": dict(routed_scale=1.0),
           "every_group_eligible": dict(groups_kept=4),
           "rope_theta": dict(rope_theta=1e4),
           "no_latent_layer": dict(layer_kinds=("kda", "kda", "kda", "kda")),
           "a_second_dense_layer": dict(layer_dense=(True, False, False, True))},
    remat_off=dict(remat=False),
    adamw_seed=2**31 + 7,
    decayed_only=(("layer_1", "router_bias"),),
    gauges={"as-rehearsed": _ling_gauges(4, 32, 0),
            "channels-that-tile": _ling_gauges(8, 32, 3),
            "tokens-that-do-not": _ling_gauges(8, 12, 0)},
    foreign_kinds=(("kda", "mamba", "mla", "kda"), "mamba"),
    control_seed=2**31 + 35,
    unchanged_seed=2**31 + 99,
    cli_seed=2**31 + 5,
)


# ---- kanana-2-30b-a3b ------------------------------------------------------------------------

KANANA_ROOFLINES = ("flash_fwd_global_roofline", "flash_bwd_dkv_global_roofline",
                    "flash_bwd_dq_global_roofline")


def _hlo_name(n, result):
    return (f"%attention_global.{n} = {result} custom-call(s32[1,1]{{1,0}} %a, "
            "s32[1,1]{1,0} %b), custom_call_target=\"tpu_custom_call\"")[:trace_reduce.NAME_CUT]


_BF = "{2,1,0:T(8,128)(2,1)}"
KANANA_TRACED = {
    # as the device trace names them: the start of the op's HLO text
    "fwd": _hlo_name(3, f"(bf16[32,8192,128]{_BF}, f32[32,8192,128]{{2,1,0:T(8,128)}})"),
    "dkv": _hlo_name(4, f"(bf16[32,8192,192]{_BF}, bf16[32,8192,128]{_BF})"),
    "dq": _hlo_name(5, f"bf16[32,8192,192]{_BF}"),
}


def _kanana_flops(flops, sizes):
    d, s = 2048, 8192
    pairs = s * (s + 1) // 2
    mla = s * (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d) + pairs * 32 * 320
    experts = s * (d * 128 + 6 * 16 / 128 * 3 * d * 768 + 3 * d * 1536)
    want = s * d * 16032 + 6 * mla + s * 3 * d * 6144 + 5 * experts
    yield "forward", flops.forward_macs(sizes), pytest.approx(want, rel=1e-12)
    yield "a step", flops.train_flops_per_sample(sizes), pytest.approx(6 * want, rel=1e-12)
    yield "a step, about", 26.5e12 < flops.train_flops_per_sample(sizes) < 27.5e12, True
    # attention's pairs and MLA's own products are three quarters of the step
    yield "the mixers' share", 0.7 < 6 * mla / want < 0.8, True
    for kernel, macs in (("fwd", 192 + 128), ("dkv", 2 * 192 + 2 * 128),
                         ("dq", 2 * 192 + 128)):
        work, nbytes = flops.kernel_call(sizes, kernel)
        yield kernel, work, 2 * macs * pairs * 32
        # the pairs lead: the bytes would take under a fifth of the products' time
        yield f"{kernel}: the pairs lead", nbytes / 819e9 < 0.2 * work / 197e12, True
    yield "the forward's bytes", flops.kernel_call(sizes, "fwd")[1], \
        32 * s * (2 * (2 * 192 + 2 * 128) + 4)


def _kanana_readers(kernel, cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    metric = dict(zip(KANANA_TRACED, KANANA_ROOFLINES))[kernel]
    ms = {"fwd": 7.0, "dkv": 12.0, "dq": 9.5}
    ops = {KANANA_TRACED[k]: v for k, v in ms.items()}
    second = KANANA_TRACED[kernel].replace(".", ".1", 1)     # a second layer's call
    ops.update({second: ms[kernel] + 1.0, "%fusion.7 = bf16[32,8192,192]{2,1,0}": 50.0,
                "%attention_global_other.2 = bf16[32,8192,192]{2,1,0}": 50.0,
                "%attention_global.9 = f32[8]{0} custom-call": 50.0})
    run = _traced(ops, flops, sizes)
    work, nbytes = flops.kernel_call(sizes, kernel)
    share = 100 * 2 * max(work / 197e12, nbytes / 819e9) / ((2 * ms[kernel] + 1.0) / 1e3)
    yield metric, run, pytest.approx(share)
    yield metric, run, pytest.approx(65, abs=35)   # 30 < got < 100
    # the whole-sequence kernels' milliseconds are still the sum over the name
    yield "attention_global_ms_per_step", run, pytest.approx(
        sum(ms.values()) + ms[kernel] + 1.0 + 50.0)
    # no trace, a rehearsal (no peaks), a run of another cell, a program with
    # no such kernel
    empties = [dict(run, peaks=None), dict(run, flops_per_sample=1.0),
               dict(run, trace={"ops_ms_per_step": {"%fusion.1": 1.0}})]
    if kernel != "dq":  # a tuple's name cut before its second array does not say
        empties.append(dict(run, trace={"ops_ms_per_step": {KANANA_TRACED[kernel][:60]: 5.0}}))
    yield from _silent((metric,), *empties)


KANANA = Decoder(
    cell_name="kanana-2-30b-a3b-atc-warmup-b1-s8k-1chip",
    catalog="kanana-2-30b-a3b-instruct-2601",
    cut={"num_hidden_layers": 6, "n_routed_experts": 16, "vocab_size": 16032},
    reduced=["num_hidden_layers", "n_routed_experts", "vocab_size"],
    cut_also=("parameters",),
    published_stated={"num_hidden_layers": 48, "n_routed_experts": 128,
                      "vocab_size": 128256},
    sizes_say={"num_experts": 128, "num_experts_held": 16,
               "published_layer_index": [0, 1, 2, 3, 4, 5]},
    marks={"deployment": ("one chip of 8", "stage of eight"),
           "expert_load": ("an eighth", "6,144"),
           "assumed": ("`head_dim` 64 is read as the rotary's width",
                       "one gated MLP of 2 x 768", "noaux_tc", "balancing update",
                       "rotates the pairs in place", "no YaRN factor",
                       "multi-token prediction", "sqrt(2 x 48)", "uniform in [-0.05, 0.05]",
                       "AdamW 3e-4", "recomputed", "segment_ids")},
    mix_as=("laguna-xs.2-atc-warmup-b1-s8k-1chip", ()),
    per_layer={"train_step_host_ms_per_step", "attention_ms_per_step",
               "attention_global_ms_per_step", "attention_proj_ms_per_step",
               "latent_proj_ms_per_step", "expert_ms_per_step",
               "expert_dispatch_ms_per_step", "mlp_ms_per_step", "head_loss_ms_per_step",
               "optimizer_ms_per_step", "recompute_ms_per_step", *KANANA_ROOFLINES},
    parameters=(("a latent mixer", _mixer(3), 26_345_984),
                ("an expert layer", _layer(1, but=_FFN), 85_196_928),
                ("the dense layer", _layer(0), 64_098_816),
                ("a layer with experts", _layer(5), 111_547_008),
                ("the embedding, the head, the last norm", _ENDS, 65_669_120),
                ("the cut", _ALL, 687_502_976)),
    flops=_kanana_flops,
    readers={k: functools.partial(_kanana_readers, k) for k in KANANA_TRACED},
    router_biases=2,
    no_leaf_named=("gate", "kda"),
    rules={"half_split_pairs": dict(rotary_interleaved=False),
           "routed_scale": dict(routed_scale=1.0), "rope_theta": dict(rope_theta=1e4),
           "a_head_gate": dict(head_gate=True),
           "a_second_dense_layer": dict(layer_dense=(True, False, True)),
           "one_shared_expert": dict(shared_dff=32), "top_k": dict(top_k=4)},
    remat_off=dict(remat=False),
    adamw_seed=2**31 + 7,
    decayed_only=(("layer_1", "router_bias"),),
    gauges={"as-rehearsed": dict(sizes={}, fields={}, tokens=32, wanted={
        "mla.layers": 3, "mla.kv_rank": 32, "mla.qk_dims": 24, "mla.v_dims": 16,
        "mla.head_gate": 0, "mla.rotary_interleaved": 1, "attention.layers_global": 3,
        "attention.heads_global": 4, "moe.score": 1, "moe.groups": 1,
        "moe.groups_kept": 1, "moe.shared_width": 64, "moe.routed_scale": 2.448,
        "moe.dense_layers": 1, "moe.experts_held": 4, "moe.experts_total": 16,
        "moe.top_k": 3, "lm.tied_head": 0, "lm.remat_blocks": 3,
        "lm.remat_kept_names": 3,
        # bfloat16 of the tokens: three layers' [heads, T, 16] and float32 [heads, T]
        "lm.remat_kept_mb": 3 * 32 * 4 * (32 + 4) / 1e6})},
    gauges_absent=("kda.",),      # Ling's class with no delta-rule layer sets none of its
    foreign_kinds=(("mla", "mamba", "mla"), "mamba"),
    # at hidden 64 held expert 0 of layer 1 gets no token (its bias is the
    # lowest and the scores spread no wider); a seed under which bfloat16 sends
    # it one (2**31 + 35) reads 0.1 on `delta_norm_gap`: the configuration's
    # `rehearsal_note`
    control_seed=2**31 + 5,
    cli_seed=2**31 + 5,
)

# ---- lfm2-24b-a2b ------------------------------------------------------------------------------


def _lfm2_gauges(tokens, kernel_layers):
    """Published layers 1-3 at hidden 128: a conv layer with the dense MLP, an
    attention layer and a conv layer with experts.  128 channels are a whole
    lane block; 12 tokens for 32 are no whole 8-row tiles."""
    return dict(sizes={}, fields={}, tokens=tokens, wanted={
        "mixer.layers_short_conv": 2, "mixer.layers_attention": 1, "short_conv.taps": 3,
        "short_conv.kernel_layers": kernel_layers, "attention.qk_norm": 1,
        "attention.layers_global": 1, "attention.heads_global": 4,
        "attention.kv_heads": 2, "attention.scale": 32 ** -0.5, "moe.score": 1,
        "moe.groups": 1, "moe.groups_kept": 1, "moe.shared_width": 0,
        "moe.routed_scale": 1, "moe.dense_layers": 1, "moe.experts_held": 4,
        "moe.experts_total": 16, "moe.top_k": 4, "lm.tied_head": 1,
        "lm.remat_blocks": 3, "lm.remat_kept_names": 4,
        # bfloat16 of the tokens: the attention layer's [4, T, 32] and float32
        # [4, T], three layers' [T, 128], the dense layer's [T, 192]
        "lm.remat_kept_mb": tokens * (256 + 16 + 768 + 384) / 1e6})


def _lfm2_flops(flops, sizes):
    d, s = 2048, 8192
    pairs = s * (s + 1) // 2
    conv = s * (d * 6144 + d * d + 5 * d)           # in, out, three taps and two gates
    attention = s * (2 * d * 2048 + 2 * d * 512) + pairs * 32 * 2 * 64
    experts = s * (d * 64 + 4 * 8 / 64 * 3 * d * 1536)
    want = s * d * 8192 + 6 * conv + 2 * attention + s * 3 * d * 11776 + 7 * experts
    yield "the layers held", flops.layers(sizes), (6, 2, 1, 7)
    yield "forward", flops.forward_macs(sizes), pytest.approx(want, rel=1e-12)
    yield "a step", flops.train_flops_per_sample(sizes), pytest.approx(6 * want, rel=1e-12)
    yield "a forward pass, about", 2 * want, pytest.approx(4.56e12, rel=2e-3)
    # the issue's split of a forward pass, in TFLOP
    yield "six short-conv mixers", 2 * 6 * conv, pytest.approx(1.65e12, rel=2e-3)
    yield "two attention layers", 2 * 2 * attention, pytest.approx(0.89e12, rel=5e-3)
    yield "the mixers' share", 0.54 < (6 * conv + 2 * attention) / want < 0.58, True
    # a kernel call: B, C, x in and y out forward; dy beside them in, three out backward
    yield "the forward kernel", flops.kernel_call(sizes, "fwd"), \
        (s * d * (2 + 6), s * d * 2 * 4)
    yield "the forward's bytes", flops.kernel_call(sizes, "fwd")[1], 134_217_728
    yield "the backward kernel", flops.kernel_call(sizes, "bwd"), \
        (s * d * (2 + 6 + 6 + 3 + 6), s * d * 2 * 7)
    for kernel in ("fwd", "bwd"):  # bound by the bytes, as counted
        work, nbytes = flops.kernel_call(sizes, kernel)
        yield f"{kernel}: the bytes lead", nbytes / 819e9 > 10 * work / 197e12, True


def _lfm2_readers(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    op = lambda name, path: types.SimpleNamespace(
        name=name, path=path, within=None, recomputed=False)
    ops = {"%short_conv_fwd.3 = bf16[1,8192,2048]{2,1,0:T(8,128)(2,1)} custom-call(": 0.40,
           "%short_conv_fwd.4": 0.44, "%short_conv_bwd.1 = (bf16[1,8192,6144]{2,1,0": 0.90,
           "%attention_global.2": 7.0, "%fusion.9": 100.0, "%short_conv_fwd_other": 50.0,
           "%fusion.1": 2.0, "%fusion.2": 3.0, "%fusion.3": 5.0}
    root = "jit(local_step)/forward_backward/layer_2/mixer/"
    record = [(op("short_conv_fwd.3", root + "short_conv_gate/pallas_call"), "x", 0.40),
              (op("short_conv_fwd.4", "jit(local_step)/rematted_computation/layer_2/mixer/"
                  "short_conv_gate/pallas_call"), "x", 0.44),
              (op("short_conv_bwd.1", root + "short_conv_gate/pallas_call"), "x", 0.90),
              (op("fusion.1", root + "short_conv_in_proj/in_proj/dot_general"), "x", 2.0),
              (op("fusion.2", root + "short_conv_out_proj/out_proj/dot_general"), "x", 3.0),
              (op("fusion.3", root + "attention_qk_norm/q_norm/mul"), "x", 5.0),
              (op("fusion.9", root + "o/dot_general"), "attention_proj", 100.0)]
    memo = lambda ops_: {step_scopes.MEMO: {
        "ops": ops_, "groups": {}, "recomputed": 0.0, "found": 0.0}}
    run = _traced(ops, flops, sizes, **memo(record))
    yield "short_conv_kernels_ms_per_step", run, pytest.approx(1.74)
    yield "short_conv_mixer_ms_per_step", run, pytest.approx(0.40 + 0.44 + 0.90 + 2.0 + 3.0)
    yield "attention_global_ms_per_step", run, 7.0
    for kernel, ms, calls in (("fwd", 0.84, 2), ("bwd", 0.90, 1)):
        work, nbytes = flops.kernel_call(sizes, kernel)
        ideal = calls * max(work / 197e12, nbytes / 819e9)
        yield f"short_conv_{kernel}_roofline", run, pytest.approx(100 * ideal / (ms / 1e3))
    yield "short_conv_fwd_roofline", run, pytest.approx(39.0, abs=0.1)  # 2 x 0.164 of 0.84 ms
    # a program without such kernels or without a record of its step (the
    # parent's), a run without a trace, a rehearsal, a run of another cell
    bare = _traced({"%fusion": 1.0}, flops, sizes, **memo([]))
    yield from _silent(("short_conv_fwd_roofline", "short_conv_bwd_roofline"), bare,
                       dict(run, peaks=None), dict(run, flops_per_sample=1.0))
    yield from _silent(("short_conv_kernels_ms_per_step", "short_conv_mixer_ms_per_step"),
                       bare)


LFM2 = Decoder(
    cell_name="lfm2-24b-a2b-atc-warmup-b1-s8k-1chip",
    catalog="LFM2-24B-A2B",
    cut={"num_hidden_layers": 8, "num_experts": 8, "vocab_size": 8192},
    reduced=["num_hidden_layers", "num_experts", "vocab_size"],
    cut_also=("parameters",),
    published_stated={"num_hidden_layers": 40, "num_experts": 64, "vocab_size": 65536},
    sizes_say={"num_experts": 64, "num_experts_held": 8,
               "published_layer_index": [1, 2, 3, 4, 5, 6, 7, 8]},
    marks={"deployment": ("one chip of 8", "stage of five"),
           "expert_load": ("an eighth", "4,096"),
           "assumed": ("tied to the embedding", "2048 / 32 = 64", "half-split pairs",
                       "[B, C, x]", "their sum + 1e-6", "balancing update",
                       "`num_dense_layers` 2", "embedding_norm", "sqrt(2 x 40)",
                       "uniform in [-0.05, 0.05]", "AdamW 3e-4", "recomputed",
                       "segment_ids")},
    mix_as=("laguna-xs.2-atc-warmup-b1-s8k-1chip", ()),
    per_layer={"train_step_host_ms_per_step", "attention_ms_per_step",
               "attention_global_ms_per_step", "attention_proj_ms_per_step",
               "expert_ms_per_step", "expert_dispatch_ms_per_step", "mlp_ms_per_step",
               "head_loss_ms_per_step", "optimizer_ms_per_step", "recompute_ms_per_step",
               # this configuration's own
               "short_conv_mixer_ms_per_step", "short_conv_kernels_ms_per_step",
               "short_conv_fwd_roofline", "short_conv_bwd_roofline"},
    parameters=(("a short-convolution mixer", _mixer(0), 16_783_360),
                ("an attention mixer", _mixer(1), 10_485_888),
                ("the dense layer", _layer(0), 89_139_200),
                ("an expert layer's feed-forward", _layer(1, but=_FFN), 75_628_608),
                ("a conv layer with experts", _layer(2), 92_416_064),
                ("an attention layer with experts", _layer(1), 86_118_592),
                ("the embedding, which is the head, and the last norm", _ENDS, 16_779_264),
                ("the cut", _ALL, 740_235_968)),
    flops=_lfm2_flops,
    readers={"short-conv": _lfm2_readers},
    router_biases=2,
    no_leaf_named=("shared", "head", "conv_bias"),
    rules={"rope_theta": dict(rope_theta=1e4), "routed_scale": dict(routed_scale=2.0),
           "top_k": dict(top_k=2), "a_shared_expert": dict(shared_dff=32),
           "an_untied_head": dict(tie_embeddings=False),
           "a_second_dense_layer": dict(layer_dense=(True, True, False)),
           "no_attention_layer": dict(layer_kinds=("conv", "conv", "conv"))},
    remat_off=dict(remat=False),
    adamw_seed=2**31 + 7,
    decayed_only=(("layer_1", "router_bias"),),
    gauges={"as-rehearsed": _lfm2_gauges(32, 2),
            "tokens-that-do-not-tile": _lfm2_gauges(12, 0)},
    gauges_absent=("ssm.", "kda.", "mla."),
    foreign_kinds=(("conv", "mamba", "attention"), "mamba"),
    control_seed=2**31 + 35,
    unchanged_seed=2**31 + 99,
    cli_seed=2**31 + 5,
)

# ---- qwen3-next-80b-a3b ------------------------------------------------------------------------


def _qwen_gauges(tokens, conv_kernel_layers):
    """Published layers 2 and 3 at hidden 128: a gated-delta-rule layer of 2
    value heads on 1 key head of 128 (a whole lane block a head: the stage's
    kernels) and a gated attention layer.  12 tokens for 32 are no whole 8-row
    tiles for the convolution's kernels."""
    return dict(sizes={}, fields={}, tokens=tokens, wanted={
        "gdn.layers": 1, "gdn.heads": 2, "gdn.key_heads": 1, "gdn.chunk": 32,
        "gdn.kernel_layers": 1, "gdn.conv_kernel_layers": conv_kernel_layers,
        "attention.gate": 1, "attention.qk_norm": 1, "attention.rotary_dims": 8,
        "attention.layers_global": 1, "attention.heads_global": 4,
        "attention.kv_heads": 2, "attention.scale": 32 ** -0.5, "moe.score": 0,
        "moe.groups": 1, "moe.groups_kept": 1, "moe.shared_width": 32,
        "moe.shared_gate": 1, "moe.routed_scale": 1, "moe.dense_layers": 0,
        "moe.experts_held": 4, "moe.experts_total": 16, "moe.top_k": 4,
        "lm.tied_head": 0, "lm.remat_blocks": 2, "lm.remat_kept_names": 4,
        # bfloat16 of the tokens: the attention layer's [4, T, 32] and float32
        # [4, T], the delta rule's [T, 2 x 128], two layers' [T, 128]
        "lm.remat_kept_mb": tokens * (256 + 16 + 512 + 512) / 1e6})


def _qwen_flops(flops, sizes):
    d, s = 2048, 8192
    vis, pairs = 64 * 65 // 2, s * (s + 1) // 2
    intra_fwd = 2 * (2 * vis - 64) * 128 + 4 * vis * 256      # two key heads, four value heads
    chunk_fwd = 4 * (3 * 64 * 128 * 128 + vis * 128)
    delta = 128 * 8 * (intra_fwd + chunk_fwd)
    gdn = s * (d * 12288 + d * 64 + 4096 * d) + delta
    attention = s * (3 * d * 4096 + 2 * d * 512) + pairs * 16 * 512
    experts = s * (d * 512 + 10 * 32 / 512 * 3 * d * 512 + 3 * d * 512 + d)
    want = s * d * 18992 + 3 * gdn + attention + 4 * experts
    yield "the layers held", flops.kinds(sizes), ["gdn", "gdn", "gdn", "attention"]
    yield "forward", flops.forward_macs(sizes), pytest.approx(want, rel=1e-12)
    yield "a step", flops.train_flops_per_sample(sizes), pytest.approx(6 * want, rel=1e-12)
    yield "a step, about", 6 * want, pytest.approx(11.39e12, rel=2e-3)
    # the issue's split of a token's forward pass, in M multiply-accumulates (its
    # delta rule counted the chunk's whole square: 2.9 where the visible pairs are 2.1)
    yield "a linear mixer", gdn / s, pytest.approx(35.8e6, rel=5e-3)
    yield "the attention mixer", attention / s, pytest.approx(60.8e6, rel=5e-3)
    yield "a feed-forward part", experts / s, pytest.approx(6.2e6, rel=1e-2)
    yield "the mixers' share", 0.71 < (3 * gdn + attention) / want < 0.74, True
    yield "the stage's forward kernel", flops.kernel_macs(sizes, "intra_fwd"), intra_fwd
    yield "the walk's forward kernel", flops.kernel_macs(sizes, "chunk_fwd"), chunk_fwd
    yield "the walk's backward kernel", flops.kernel_macs(sizes, "chunk_bwd"), \
        4 * (7 * 64 * 128 * 128 + 2 * vis * 128)
    ling = manifest.resolve(LING.cell_name)     # the walk's kernels are Ling's, to the byte
    for kernel in ("fwd", "bwd"):
        yield f"the walk's {kernel} call is Ling's", flops.kernel_call(sizes, "chunk_" + kernel), \
            ling.module("flops").kernel_call(ling.sizes(), kernel)
    # a call of the stage forward: q, k of two key heads, v, g, beta in; the six out
    read = 2 * 2 * 64 * 128 * 2 + 4 * 64 * 128 * 2 + 2 * 4 * 64 * 4
    walked = 4 * (3 * 64 * 128 * 2 + 64 * 128 * 4 + 64 * 64 * 4 + 128 * 4)
    yield "the stage's forward call", flops.kernel_call(sizes, "intra_fwd"), \
        (2 * intra_fwd * 128, 128 * (read + walked))
    yield "the stage's backward bytes", flops.kernel_call(sizes, "intra_bwd")[1], \
        128 * (2 * read + walked)
    for kernel in ("intra_fwd", "intra_bwd", "chunk_fwd", "chunk_bwd"):
        work, nbytes = flops.kernel_call(sizes, kernel)
        yield f"{kernel}: the bytes lead", nbytes / 819e9 > 4 * work / 197e12, True


def _qwen_readers(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    op = lambda name, path, within=None: types.SimpleNamespace(
        name=name, path=path, within=within, recomputed=False)
    ops = {"%gdn_intra_fwd.3 = (bf16[...]": 4.0, "%gdn_intra_fwd.4": 6.0,
           "%gdn_intra_bwd.1": 9.0, "%kda_chunk_fwd.7": 3.0, "%kda_chunk_bwd.2": 8.0,
           "%attention_global.2": 7.0, "%fusion.9": 100.0, "%gdn_intra_fwd_other": 50.0,
           "%fusion.1": 2.0, "%fusion.2": 3.0, "%fusion.3": 5.0}
    root = "jit(local_step)/forward_backward/layer_2/mixer/"
    loop = root + "gdn_chunk/while/body"
    record = [(op("gdn_intra_fwd.3", loop + "/gdn_intra", "while.1"), "x", 4.0),
              (op("gdn_intra_fwd.4", loop + "/gdn_intra", "while.2"), "x", 6.0),
              (op("gdn_intra_bwd.1", loop + "/gdn_intra", "while.3"), "x", 9.0),
              (op("kda_chunk_fwd.7", loop, "while.1"), "x", 3.0),
              (op("kda_chunk_bwd.2", loop, "while.3"), "x", 8.0),
              (op("fusion.1", loop + "/gdn_intra/transpose", "while.3"), "x", 2.0),
              (op("fusion.2", root + "gdn_in_proj/gdn_qkvz/dot_general"), "x", 3.0),
              (op("fusion.3", root + "attention_gate/mul"), "attention_proj", 5.0),
              (op("fusion.9", root + "o/dot_general"), "attention_proj", 100.0)]
    memo = lambda ops_: {step_scopes.MEMO: {
        "ops": ops_, "groups": {}, "recomputed": 0.0, "found": 0.0}}
    run = _traced(ops, flops, sizes, **memo(record))
    yield "gdn_kernels_ms_per_step", run, 30.0
    yield "gdn_mixer_ms_per_step", run, 5.0
    yield "attention_global_ms_per_step", run, 7.0
    trips = 32 // flops.HEADS_A_CALL
    for kernel, ms, sites in (("intra_fwd", 10.0, 2), ("intra_bwd", 9.0, 1),
                              ("chunk_fwd", 3.0, 1), ("chunk_bwd", 8.0, 1)):
        work, nbytes = flops.kernel_call(sizes, kernel)
        ideal = sites * trips * max(work / 197e12, nbytes / 819e9)
        yield f"gdn_{kernel}_roofline", run, pytest.approx(100 * ideal / (ms / 1e3))
    # eight trips of 0.0826 ms, twice, in 10 ms
    yield "gdn_intra_fwd_roofline", run, pytest.approx(13.2, abs=0.1)
    # a program without such kernels or without a record of its step (the
    # parent's), a run without a trace, a rehearsal, a run of another cell
    bare = _traced({"%fusion": 1.0}, flops, sizes, **memo([]))
    yield from _silent(tuple(f"gdn_{k}_roofline" for k in (
        "intra_fwd", "intra_bwd", "chunk_fwd", "chunk_bwd")), bare,
        dict(run, peaks=None), dict(run, flops_per_sample=1.0))
    yield from _silent(("gdn_kernels_ms_per_step", "gdn_mixer_ms_per_step"), bare)


QWEN3_NEXT = Decoder(
    cell_name="qwen3-next-80b-a3b-atc-warmup-b1-s8k-1chip",
    catalog="Qwen3-Next-80B-A3B-Instruct",
    cut={"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992},
    reduced=["num_hidden_layers", "num_experts", "vocab_size"],
    cut_also=("parameters",),
    published_stated={"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936},
    sizes_say={"num_experts": 512, "num_experts_held": 32,
               "published_layer_index": [0, 1, 2, 3], "gdn_chunk_size": 64},
    marks={"deployment": ("one chip of 16", "one of 8", "stage of twelve"),
           "expert_load": ("a sixteenth", "5,120"),
           "assumed": ("(i + 1) % `full_attention_interval`", "`attention_bias`",
                       "multi-token prediction", "auxiliary", "groups the columns by key head",
                       "the norm before the gate", "uniform in (0, 16)", "`dt_bias` ones",
                       "(1 + w)", "channel i with channel i + 32", "sqrt(2 x 48)",
                       "AdamW 3e-4", "recomputed", "segment_ids")},
    mix_as=("laguna-xs.2-atc-warmup-b1-s8k-1chip", ()),
    per_layer={"train_step_host_ms_per_step", "attention_ms_per_step",
               "attention_global_ms_per_step", "attention_proj_ms_per_step",
               "expert_ms_per_step", "expert_dispatch_ms_per_step", "mlp_ms_per_step",
               "head_loss_ms_per_step", "optimizer_ms_per_step", "recompute_ms_per_step",
               # this configuration's own
               "gdn_mixer_ms_per_step", "gdn_kernels_ms_per_step",
               "gdn_intra_fwd_roofline", "gdn_intra_bwd_roofline",
               "gdn_chunk_fwd_roofline", "gdn_chunk_bwd_roofline"},
    parameters=(("a gated-delta-rule mixer", _mixer(0), 33_718_464),
                ("the gated attention mixer", _mixer(3), 27_263_488),
                ("a layer's feed-forward part", _layer(0, but=_FFN), 104_859_648),
                ("a linear layer", _layer(1), 138_582_208),
                ("the attention layer", _layer(3), 132_127_232),
                ("the embedding, the head, the last norm", _ENDS, 77_793_280),
                ("the cut", _ALL, 625_667_136)),
    flops=_qwen_flops,
    readers={"gated-delta-rule": _qwen_readers},
    no_leaf_named=("router_bias", "conv_bias", "layer_0/mlp/"),
    rules={"rope_theta": dict(rope_theta=1e4), "the_whole_head_turned": dict(rotary_dims=32),
           "top_k": dict(top_k=2), "a_wider_shared_expert": dict(shared_dff=16),
           "a_tied_head": dict(tie_embeddings=True),
           "no_attention_layer": dict(layer_kinds=("gdn", "gdn"))},
    remat_off=dict(remat=False),
    adamw_seed=2**31 + 7,
    gauges={"as-rehearsed": _qwen_gauges(32, 1),
            "tokens-that-do-not-tile": _qwen_gauges(12, 0)},
    gauges_absent=("ssm.", "kda.", "mla.", "short_conv."),
    foreign_kinds=(("gdn", "kda"), "kda"),
    control_seed=2**31 + 35,
    unchanged_seed=2**31 + 99,
    cli_seed=2**31 + 5,
)

TABLE = (SMALLTHINKER, LAGUNA, GRANITE, LING, KANANA, LFM2, QWEN3_NEXT)


def each(field=None):
    """One case a configuration that has `field`, by the configuration's name."""
    entries = [e for e in TABLE if field is None or getattr(e, field)]
    return pytest.mark.parametrize("entry", entries, ids=[e.name for e in entries])


def each_of(field):
    """One case a key of a configuration's `field`, `<configuration>-<key>`."""
    return pytest.mark.parametrize("entry,key", [
        pytest.param(e, k, id=f"{e.name}-{k}") for e in TABLE for k in getattr(e, field)])
