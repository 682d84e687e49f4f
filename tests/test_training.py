"""SPMD train-step builder tests: the flagship composition (grads + gossip
in one jitted program) must train and keep ranks in consensus."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import topology_util as tu
from bluefog_tpu.core import basics
from bluefog_tpu.models import LeNet5, ResNet18
from bluefog_tpu.optim import CommunicationType
from bluefog_tpu.training import make_decentralized_train_step, replicate_for_mesh

SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(devices):
    bf.init(local_size=2)
    yield
    bf.shutdown()


def _mlp_apply(variables, x):
    p = variables["params"]
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return h @ p["w2"] + p["b2"]


def _mlp_params(rng, din=8, dh=16, nclass=4):
    k1, k2 = jax.random.split(rng)
    return {
        "w1": jax.random.normal(k1, (din, dh)) * 0.3,
        "b1": jnp.zeros((dh,)),
        "w2": jax.random.normal(k2, (dh, nclass)) * 0.3,
        "b2": jnp.zeros((nclass,)),
    }


@pytest.mark.parametrize(
    "comm",
    [
        CommunicationType.neighbor_allreduce,
        CommunicationType.allreduce,
        CommunicationType.empty,
    ],
)
def test_train_step_decreases_loss(comm):
    ctx = basics.context()
    params = replicate_for_mesh(_mlp_params(jax.random.PRNGKey(0)), SIZE)
    init_fn, step_fn = make_decentralized_train_step(
        _mlp_apply,
        optax.sgd(0.1),
        ctx.mesh,
        communication_type=comm,
        plan=ctx.plan if comm == CommunicationType.neighbor_allreduce else None,
        donate=False,
    )
    state = init_fn(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(SIZE, 16, 8)).astype(np.float32))
    # learnable task: labels are a fixed linear function of the inputs, so
    # the consensus model can fit every rank's shard simultaneously
    w_true = rng.normal(size=(8, 4)).astype(np.float32)
    y = jnp.asarray(np.argmax(np.asarray(x) @ w_true, axis=-1), jnp.int32)
    bs = {}
    losses = []
    for _ in range(30):
        params, bs, state, loss, acc = step_fn(params, bs, state, x, y)
        losses.append(float(np.asarray(loss).mean()))
    assert losses[-1] < losses[0] * 0.7, losses[:: len(losses) - 1]
    if comm != CommunicationType.empty:
        spread = max(
            float(np.asarray(l).std(axis=0).max())
            for l in jax.tree_util.tree_leaves(params)
        )
        assert spread < 0.1


def test_train_step_hierarchical_mesh():
    ctx = basics.context()
    params = replicate_for_mesh(_mlp_params(jax.random.PRNGKey(1)), SIZE)
    init_fn, step_fn = make_decentralized_train_step(
        _mlp_apply,
        optax.sgd(0.05),
        ctx.hier_mesh,
        communication_type=CommunicationType.hierarchical_neighbor_allreduce,
        machine_plan=ctx.machine_plan,
        donate=False,
    )
    state = init_fn(params)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(SIZE, 8, 8)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 4, size=(SIZE, 8)), jnp.int32)
    params, bs, state, loss, _ = step_fn(params, {}, state, x, y)
    # locals of each machine identical after hierarchical gossip
    w1 = np.asarray(params["w1"])
    for m in range(SIZE // 2):
        np.testing.assert_allclose(w1[2 * m], w1[2 * m + 1], rtol=1e-5)


def test_train_step_with_batch_stats_resnet():
    ctx = basics.context()
    model = ResNet18(num_classes=4, num_filters=4, small_images=True)
    x0 = jnp.ones((2, 8, 8, 3))
    variables = model.init(jax.random.PRNGKey(0), x0, train=True)
    params = replicate_for_mesh(variables["params"], SIZE)
    bstats = replicate_for_mesh(variables["batch_stats"], SIZE)
    init_fn, step_fn = make_decentralized_train_step(
        model.apply,
        optax.sgd(0.01),
        ctx.mesh,
        communication_type=CommunicationType.neighbor_allreduce,
        plan=ctx.plan,
        has_batch_stats=True,
        donate=False,
    )
    state = init_fn(params)
    batch = jnp.ones((SIZE, 2, 8, 8, 3))
    labels = jnp.zeros((SIZE, 2), jnp.int32)
    params, bstats, state, loss, _ = step_fn(params, bstats, state, batch, labels)
    assert np.isfinite(np.asarray(loss)).all()
    # batch stats must have moved off init (local BN updates ran)
    moved = any(
        float(jnp.abs(np.asarray(l)).max()) > 0
        for l in jax.tree_util.tree_leaves(bstats)
    )
    assert moved


def test_models_forward_shapes():
    le = LeNet5()
    v = le.init(jax.random.PRNGKey(0), jnp.zeros((2, 28, 28, 1)))
    out = le.apply(v, jnp.zeros((2, 28, 28, 1)))
    assert out.shape == (2, 10)
    rn = ResNet18(num_classes=7, num_filters=4, small_images=True)
    v = rn.init(jax.random.PRNGKey(0), jnp.zeros((2, 16, 16, 3)), train=True)
    out = rn.apply(v, jnp.zeros((2, 16, 16, 3)), train=False)
    assert out.shape == (2, 7)
    assert out.dtype == jnp.float32


def test_vit_forward_and_decentralized_step():
    """ViT family: forward shape + a decentralized ATC train step on the
    8-device mesh (shares the ResNet harness; no batch stats)."""
    from bluefog_tpu.models import ViT

    vit = ViT(num_classes=5, patch_size=4, hidden_size=32, num_layers=2,
              num_heads=4, dff=64)
    v = vit.init(jax.random.PRNGKey(0), jnp.zeros((2, 16, 16, 3)))
    out = vit.apply(v, jnp.zeros((2, 16, 16, 3)))
    assert out.shape == (2, 5)
    assert out.dtype == jnp.float32

    ctx = basics.context()
    init_fn, step_fn = make_decentralized_train_step(
        vit.apply, optax.sgd(0.05), ctx.mesh,
        communication_type=CommunicationType.neighbor_allreduce,
        plan=ctx.plan,
        donate=False,
    )
    params = replicate_for_mesh(v["params"], SIZE)
    opt_state = init_fn(params)
    rng = np.random.default_rng(0)
    batch = jnp.asarray(
        rng.normal(size=(SIZE, 2, 16, 16, 3)).astype(np.float32)
    )
    labels = jnp.asarray(rng.integers(0, 5, size=(SIZE, 2)), jnp.int32)
    bs = {}
    losses = []
    for _ in range(4):
        params, bs, opt_state, loss, _ = step_fn(
            params, bs, opt_state, batch, labels
        )
        losses.append(float(np.asarray(loss).mean()))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_steps_per_call_fused_matches_sequential():
    """k fused steps per dispatch (dispatch-cost amortization) must produce
    EXACTLY the same trajectory as k sequential single-step calls."""
    bf.set_topology(tu.RingGraph(SIZE))
    ctx = basics.context()
    rng = np.random.default_rng(3)
    params0 = replicate_for_mesh(
        _mlp_params(jax.random.PRNGKey(1)), SIZE
    )
    xb = jnp.asarray(rng.normal(size=(SIZE, 8, 8)).astype(np.float32))
    yb = jnp.asarray(rng.integers(0, 4, size=(SIZE, 8)), jnp.int32)
    x2 = jnp.asarray(rng.normal(size=(SIZE, 8, 8)).astype(np.float32))
    y2 = jnp.asarray(rng.integers(0, 4, size=(SIZE, 8)), jnp.int32)

    def make(spc):
        return make_decentralized_train_step(
            _mlp_apply, optax.sgd(0.1, momentum=0.9), ctx.mesh,
            communication_type=CommunicationType.neighbor_allreduce,
            plan=ctx.plan, donate=False, steps_per_call=spc,
        )

    init1, step1 = make(1)
    os1 = init1(params0)
    p, os_ = params0, os1
    for b, l in ((xb, yb), (x2, y2)):
        p, _, os_, loss_seq, _ = step1(p, None, os_, b, l)

    init2, step2 = make(2)
    os2 = init2(params0)
    batch = jnp.stack([xb, x2])
    labels = jnp.stack([yb, y2])
    p2, _, os2, loss_fused, _ = step2(params0, None, os2, batch, labels)

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        p, p2,
    )
    np.testing.assert_allclose(
        np.asarray(loss_seq), np.asarray(loss_fused), rtol=1e-6
    )


def test_llama_scan_layers_matches_unrolled():
    """scan_layers=True (one block body in the HLO, params stacked on a
    leading layer axis) must compute the same function as the unrolled
    model when fed the same weights."""
    from bluefog_tpu.models.transformer import LlamaLM

    kw = dict(vocab_size=97, hidden_size=32, num_layers=3, num_heads=4,
              dff=64, dtype=jnp.float32)
    ids = jnp.ones((2, 8), jnp.int32)
    m_un = LlamaLM(**kw)
    m_sc = LlamaLM(**kw, scan_layers=True, remat=True)
    p_un = m_un.init(jax.random.PRNGKey(0), ids)["params"]
    p_sc = m_sc.init(jax.random.PRNGKey(0), ids)["params"]
    blocks = sorted(
        (k for k in p_un if k.startswith("_DecoderBlock")),
        key=lambda s: int(s.split("_")[-1]),
    )
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[p_un[b] for b in blocks]
    )
    scan_key = next(k for k in p_sc if "Scan" in k)
    inner_key = next(iter(p_sc[scan_key]))
    p_sc2 = {k: p_un[k] for k in p_un if not k.startswith("_DecoderBlock")}
    p_sc2[scan_key] = {inner_key: stacked}
    out_un = m_un.apply({"params": p_un}, ids)
    out_sc = m_sc.apply({"params": p_sc2}, ids)
    np.testing.assert_allclose(
        np.asarray(out_un), np.asarray(out_sc), atol=2e-6
    )


@pytest.mark.parametrize("policy", ["dots", "attn"])
def test_llama_remat_policy_matches_full_remat(policy):
    """remat_policy changes WHAT is saved for the backward pass, never
    the function: outputs and gradients must match full remat."""
    from bluefog_tpu.models.transformer import LlamaLM

    kw = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
              dff=64, dtype=jnp.float32, scan_layers=True, remat=True)
    ids = jnp.ones((2, 8), jnp.int32)
    m_full = LlamaLM(**kw)
    m_pol = LlamaLM(**kw, remat_policy=policy)
    p = m_full.init(jax.random.PRNGKey(0), ids)["params"]

    def loss(m, p):
        return jnp.sum(m.apply({"params": p}, ids) ** 2)

    l1, g1 = jax.value_and_grad(lambda p: loss(m_full, p))(p)
    l2, g2 = jax.value_and_grad(lambda p: loss(m_pol, p))(p)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_llama_gqa_param_savings_and_equivalence():
    """num_kv_heads: fewer k/v projection params (GQA); with
    num_kv_heads == num_heads the model is EXACTLY the baseline (same
    param tree, same outputs); kv=1 (MQA) runs and differentiates."""
    from bluefog_tpu.models.transformer import LlamaLM

    kw = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
              dff=64, dtype=jnp.float32)
    ids = jnp.ones((2, 8), jnp.int32)

    base = LlamaLM(**kw)
    same = LlamaLM(**kw, num_kv_heads=4)
    p = base.init(jax.random.PRNGKey(0), ids)["params"]
    np.testing.assert_allclose(
        np.asarray(base.apply({"params": p}, ids)),
        np.asarray(same.apply({"params": p}, ids)))

    mqa = LlamaLM(**kw, num_kv_heads=1)
    p_mqa = mqa.init(jax.random.PRNGKey(0), ids)["params"]

    def count(t):
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(t))

    # per layer, k and v shrink from d*d to d*(d/4): 2 * 32*24 saved/layer
    assert count(p) - count(p_mqa) == 2 * 2 * 32 * 24

    def loss(m, pp):
        return jnp.sum(m.apply({"params": pp}, ids) ** 2)

    g = jax.grad(lambda pp: loss(mqa, pp))(p_mqa)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()

    # scan_layers + remat + GQA compose
    scan_gqa = LlamaLM(**kw, num_kv_heads=2, scan_layers=True, remat=True)
    p_s = scan_gqa.init(jax.random.PRNGKey(0), ids)["params"]
    out = scan_gqa.apply({"params": p_s}, ids)
    assert np.isfinite(np.asarray(out)).all()


def test_llama_head_chunks_matches_full():
    """The chunked LM loss (head_chunks>1: lax.scan + jax.checkpoint,
    full logits never materialized) must equal the full-logits loss —
    value AND gradients — and both must equal the external
    optax-style shifted CE the benchmark uses."""
    import optax
    from bluefog_tpu.models.transformer import LlamaLM

    kw = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
              dff=64, dtype=jnp.float32)
    m_full = LlamaLM(**kw)
    m_chunk = LlamaLM(**kw, head_chunks=4)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 97, size=(2, 16)), jnp.int32
    )
    p = m_full.init(jax.random.PRNGKey(0), ids)["params"]

    # external reference: CE over full logits, the benchmark's lm_loss
    logits = m_full.apply({"params": p}, ids)
    ref = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], ids[:, 1:]
    ).mean()

    l_full, g_full = jax.value_and_grad(
        lambda p: m_full.apply({"params": p}, ids, labels=ids)
    )(p)
    l_chunk, g_chunk = jax.value_and_grad(
        lambda p: m_chunk.apply({"params": p}, ids, labels=ids)
    )(p)
    np.testing.assert_allclose(np.asarray(l_full), np.asarray(ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(l_chunk), np.asarray(ref), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_full),
                    jax.tree_util.tree_leaves(g_chunk)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_final_quality_parity_head_to_head():
    """Upstream's core claim made a regression test (r4 verdict #4 /
    SURVEY §6 [U]): same model, same data, same seeds, fixed steps —
    gossip (neighbor_allreduce exp2) and exact gradient tracking must
    reach NEAR-IDENTICAL final eval quality to centralized allreduce,
    with consensus spread -> 0.

    Setup: small Llama on a deterministic next-token rule
    (t+1 = 3t+1 mod V), heterogeneous shards (each rank sees different
    sequences of the same rule), 120 steps through the flagship fused
    train-step program (steps_per_call batches dispatches — the eager
    per-step interleave can starve XLA:CPU's in-process rendezvous on a
    1-core host).  Measured evals: allreduce 0.274, gossip 0.265, GT
    0.241 — the decentralized methods land slightly BETTER here; the
    assert bounds |delta| either way."""
    from bluefog_tpu import algorithms
    from bluefog_tpu.models.transformer import LlamaLM
    from bluefog_tpu.training import make_lm_loss_fns

    ctx = basics.context()
    n = SIZE
    V, T, B = 32, 16, 2
    model = LlamaLM(vocab_size=V, hidden_size=24, num_layers=2,
                    num_heads=4, dff=48, dtype=jnp.float32)
    rng = np.random.default_rng(0)

    def make_seqs(k):
        starts = rng.integers(0, V, size=k)
        seqs = np.zeros((k, T), np.int64)
        seqs[:, 0] = starts
        for t in range(1, T):
            seqs[:, t] = (3 * seqs[:, t - 1] + 1) % V
        return seqs

    train = jnp.asarray(make_seqs(n * B).reshape(n, B, T), jnp.int32)
    eval_ids = jnp.asarray(make_seqs(32), jnp.int32)
    p0 = replicate_for_mesh(
        model.init(jax.random.PRNGKey(0), train[0])["params"], n)
    lm_apply, lm_loss = make_lm_loss_fns(model)
    K, CALLS, lr = 10, 12, 0.1

    def run(comm, base):
        init_fn, step_fn = make_decentralized_train_step(
            lm_apply, base, ctx.mesh, communication_type=comm,
            plan=(ctx.plan if comm == CommunicationType.neighbor_allreduce
                  else None),
            loss_fn=lm_loss, donate=False, steps_per_call=K)
        params, state, bs = p0, init_fn(p0), {}
        xb = jnp.broadcast_to(train[None], (K,) + train.shape)
        for _ in range(CALLS):
            params, bs, state, loss, _ = step_fn(params, bs, state, xb, xb)
        # one program for the means, and done before the evaluation is taken
        # operation by operation: a mean a leaf, each an all-reduce over the
        # eight devices dispatched while the last is in flight, is what the
        # CPU's rendezvous gave up on after 40 s under a loaded machine
        # ("only 7 of them arrived"), and the worker went with it
        mean_p = jax.block_until_ready(jax.jit(lambda p: jax.tree_util.tree_map(
            lambda a: a.mean(0), p))(params))
        el = float(model.apply({"params": mean_p}, eval_ids,
                               labels=eval_ids))
        spread = max(float(np.asarray(l).std(axis=0).max())
                     for l in jax.tree_util.tree_leaves(params))
        return el, spread

    ar, _ = run(CommunicationType.allreduce, optax.sgd(lr))
    nar, nar_spread = run(CommunicationType.neighbor_allreduce,
                          optax.sgd(lr))
    # GT's comm lives inside the transform; CommunicationType.empty keeps
    # the builder's combine an identity
    gt, gt_spread = run(CommunicationType.empty,
                        algorithms.gradient_tracking_spmd(lr, ctx.plan))

    assert ar < 0.6, f"allreduce baseline failed to converge: {ar}"
    assert abs(nar - ar) < 0.08, (nar, ar)
    assert abs(gt - ar) < 0.08, (gt, ar)
    assert nar_spread < 1e-2, nar_spread
    assert gt_spread < 1e-3, gt_spread


def test_llama_spmd_vocab_matches_default():
    """``spmd_vocab=True`` (one-hot-matmul embedding + one-hot target
    extraction, the vocab-sharded FSDP deployment mode) must be a pure
    re-spelling: same params tree, same loss, same gradients as the
    take/take_along_axis default — with and without the chunked head."""
    from bluefog_tpu.models.transformer import LlamaLM

    kw = dict(vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
              dff=64, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, 96, size=(2, 16)), jnp.int32
    )
    for chunks in (0, 4):
        m_ref = LlamaLM(**kw, head_chunks=chunks)
        m_spmd = LlamaLM(**kw, head_chunks=chunks, spmd_vocab=True)
        p = m_ref.init(jax.random.PRNGKey(0), ids)["params"]
        p2 = m_spmd.init(jax.random.PRNGKey(0), ids)["params"]
        assert (jax.tree_util.tree_structure(p)
                == jax.tree_util.tree_structure(p2))
        l_ref, g_ref = jax.value_and_grad(
            lambda p: m_ref.apply({"params": p}, ids, labels=ids))(p)
        l_spmd, g_spmd = jax.value_and_grad(
            lambda p: m_spmd.apply({"params": p}, ids, labels=ids))(p)
        np.testing.assert_allclose(np.asarray(l_spmd), np.asarray(l_ref),
                                   rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                        jax.tree_util.tree_leaves(g_spmd)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


def test_lm_loss_fns_chunked_honors_distinct_labels():
    """r3 advisor: make_lm_loss_fns' chunked branch must not silently train
    on inputs-as-labels when a caller passes distinct (e.g. masked) targets.
    The chunked apply_fn now accepts labels; with labels != ids it must match
    the full-logits CE on those labels, and differ from the ids-as-labels loss."""
    from bluefog_tpu.models.transformer import LlamaLM
    from bluefog_tpu.training import make_lm_loss_fns

    kw = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
              dff=64, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 97, size=(2, 16)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 97, size=(2, 16)), jnp.int32)

    m_full = LlamaLM(**kw)
    m_chunk = LlamaLM(**kw, head_chunks=4)
    p = m_full.init(jax.random.PRNGKey(0), ids)["params"]

    full_apply, full_loss = make_lm_loss_fns(m_full)
    chunk_apply, chunk_loss = make_lm_loss_fns(m_chunk)
    assert "labels" in __import__("inspect").signature(chunk_apply).parameters

    ref = full_loss(full_apply({"params": p}, ids), labels)
    got = chunk_loss(chunk_apply({"params": p}, ids, labels=labels), labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)
    ids_as_labels = chunk_loss(chunk_apply({"params": p}, ids), ids)
    assert abs(float(got) - float(ids_as_labels)) > 1e-3


def test_llama_head_kernel_pytree_path_unchanged():
    """The explicit _HeadKernel must keep the LM head at Dense_0/kernel
    with the nn.Dense shape/dtype (checkpoint compatibility)."""
    from bluefog_tpu.models.transformer import LlamaLM

    m = LlamaLM(vocab_size=97, hidden_size=32, num_layers=1, num_heads=4,
                dff=64, dtype=jnp.float32)
    p = m.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    assert p["Dense_0"]["kernel"].shape == (32, 97)
    assert p["Dense_0"]["kernel"].dtype == jnp.float32


def test_llama_head_bf16_close_to_f32():
    """head_dtype=bf16 rounds only the matmul INPUTS (f32 accumulation
    via preferred_element_type): the loss must track the f32 head to
    bf16-rounding tolerance, for both the full and chunked paths."""
    from bluefog_tpu.models.transformer import LlamaLM

    kw = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
              dff=64, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, 97, size=(2, 16)), jnp.int32
    )
    m_f32 = LlamaLM(**kw)
    p = m_f32.init(jax.random.PRNGKey(0), ids)["params"]
    l_ref, g_ref = jax.value_and_grad(
        lambda p: m_f32.apply({"params": p}, ids, labels=ids))(p)
    for hc in (0, 4):
        m_bf16 = LlamaLM(**kw, head_chunks=hc, head_dtype=jnp.bfloat16)
        got, g = jax.value_and_grad(
            lambda p: m_bf16.apply({"params": p}, ids, labels=ids))(p)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(l_ref), rtol=5e-3
        )
        # the custom VJP rounds matmul operands (incl. the cotangent) to
        # bf16; grads must stay f32-dtyped and track the f32 head to
        # bf16-rounding tolerance
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(g_ref)):
            assert a.dtype == b.dtype
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-2, rtol=2e-2
            )


# ---- the chunked head and loss: its gradient taken in the forward loop ---------


def _parents_chunked_loss(hidden, kernel, labels, num_chunks,
                          dtype=jnp.float32, onehot_targets=False,
                          kernel_constraint=None):
    """`chunked_softmax_cross_entropy` as the parent commit (0044f21) had it:
    a `jax.checkpoint` body, so that the backward loop makes each chunk's
    logits again and autodiff takes the gradient.  The oracle."""
    from bluefog_tpu.models.transformer import _head_matmul

    B, T, _ = hidden.shape
    if T % num_chunks:
        raise ValueError(f"num_chunks {num_chunks} must divide T {T}")
    # shift the targets left so every chunk scores positions uniformly;
    # the pad at T-1 carries weight 0 (the last token predicts nothing)
    y = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1)
    w = jnp.concatenate(
        [jnp.ones((B, T - 1), jnp.float32), jnp.zeros((B, 1), jnp.float32)],
        axis=1,
    )
    tc = T // num_chunks
    xs = hidden.reshape(B, num_chunks, tc, hidden.shape[-1]).transpose(1, 0, 2, 3)
    ys = y.reshape(B, num_chunks, tc).transpose(1, 0, 2)
    ws = w.reshape(B, num_chunks, tc).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, xyw):
        xc, yc, wc = xyw
        k = kernel if kernel_constraint is None else kernel_constraint(kernel)
        logits = _head_matmul(xc, k, dtype)  # [B, tc, V] — the peak
        lse = jax.nn.logsumexp(logits, axis=-1)
        if onehot_targets:
            tgt = jnp.sum(
                logits * jax.nn.one_hot(yc, logits.shape[-1],
                                        dtype=logits.dtype), axis=-1)
        else:
            tgt = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        # per-chunk outputs instead of a scalar carry: under shard_map a
        # plain-zeros carry init would mismatch the body's varying-axes
        # type (jax vma rules); stacked outputs inherit it automatically
        return carry, (((lse - tgt) * wc).sum(), wc.sum())

    _, (tots, cnts) = jax.lax.scan(body, (), (xs, ys, ws))
    return tots.sum() / cnts.sum()


def _whole_logits_loss(hidden, kernel, labels):
    logits = hidden @ kernel
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], labels[:, 1:]).mean()


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("B", [1, 2])
@pytest.mark.parametrize("onehot", [False, True], ids=["gather", "onehot"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_chunked_loss_takes_its_gradient_in_the_forward_loop(dtype, onehot, B, tied):
    """Loss and gradients of the custom rule against the parent's checkpointed
    loop and against the float32 loss over whole logits, under a cotangent of 3 with
    `hidden` used a second time; the head's tensor its own or the transpose
    of the embedding the lookup reads.  Without a gradient the value is the
    parent's to the bit."""
    from bluefog_tpu.models.transformer import chunked_softmax_cross_entropy

    T, d, V, n = 64, 32, 101, 4
    rng = np.random.default_rng(B + 2 * onehot)
    ids = jnp.asarray(rng.integers(0, V, size=(B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, V, size=(B, T)), jnp.int32)
    params = {"embed": jnp.asarray(rng.normal(size=(V, d)) * 0.3, jnp.float32),
              "mix": jnp.asarray(rng.normal(size=(d, d)) * 0.3, jnp.float32)}
    if not tied:
        params["head"] = jnp.asarray(rng.normal(size=(d, V)) * 0.3, jnp.float32)

    def objective(head_loss):
        def f(p):
            hidden = jnp.tanh(p["embed"][ids] @ p["mix"])
            kernel = p["embed"].T if tied else p["head"]
            return 3.0 * head_loss(hidden, kernel) + 0.01 * (hidden ** 2).sum()
        return jax.jit(jax.value_and_grad(f))(params)

    kw = dict(dtype=dtype, onehot_targets=onehot)
    got_loss, got = objective(
        lambda h, k: chunked_softmax_cross_entropy(h, k, labels, n, **kw))
    # bf16 operands: a rounded cotangent; against float32 logits, rounded ones
    tol, whole_tol = (1e-5, 1e-5) if dtype == jnp.float32 else (2e-2, 5e-3)
    for reference, loss_tol in (
            (lambda h, k: _parents_chunked_loss(h, k, labels, n, **kw), 1e-5),
            (lambda h, k: _whole_logits_loss(h, k, labels), whole_tol)):
        want_loss, want = objective(reference)
        np.testing.assert_allclose(got_loss, want_loss, rtol=loss_tol)
        assert set(got) == set(want)
        for name in want:
            assert got[name].dtype == want[name].dtype
            np.testing.assert_allclose(
                got[name], want[name], rtol=0,
                atol=tol * float(jnp.abs(want[name]).max()), err_msg=name)

    hidden = jnp.tanh(params["embed"][ids] @ params["mix"])
    kernel = params["embed"].T if tied else params["head"]
    plain = [jax.jit(lambda h, k, f=f: f(h, k, labels, n, **kw))(hidden, kernel)
             for f in (chunked_softmax_cross_entropy, _parents_chunked_loss)]
    assert plain[0] == plain[1]


@pytest.mark.parametrize("shared", ["kernel", "hidden"])
def test_chunked_loss_under_shard_map_with_one_operand_shared(devices, shared):
    """Data-parallel `shard_map`: the head's tensor the same on every rank
    and the batch not (or the other way round).  The kept `dx` and `dW` vary
    over the rank axis, and the shared operand's gradient comes back summed
    over it, as the parent's loop gave it."""
    from jax.sharding import Mesh, PartitionSpec as P
    from bluefog_tpu.models.transformer import chunked_softmax_cross_entropy

    R, T, d, V, n = 4, 16, 8, 11, 2
    rng = np.random.default_rng(5)
    mesh = Mesh(np.array(devices[:R]), ("r",))
    per_rank = lambda *shape: jnp.asarray(rng.normal(size=(R,) + shape), jnp.float32)
    one = lambda *shape: jnp.asarray(rng.normal(size=(1,) + shape), jnp.float32)
    hidden = one(1, T, d) if shared == "hidden" else per_rank(1, T, d)
    kernel = one(d, V) if shared == "kernel" else per_rank(d, V)
    labels = jnp.asarray(rng.integers(0, V, size=(R, 1, T)), jnp.int32)
    specs = tuple(P() if a.shape[0] == 1 else P("r") for a in (hidden, kernel))

    def ranks(head_loss):
        def local(h, k, y):
            loss, grads = jax.value_and_grad(
                lambda h, k: head_loss(h[0], k[0], y[0], n), (0, 1))(h, k)
            return loss[None], grads
        return jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=specs + (P("r"),),
            out_specs=(P("r"), specs)))(hidden, kernel, labels)

    got, want = ranks(chunked_softmax_cross_entropy), ranks(_parents_chunked_loss)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
