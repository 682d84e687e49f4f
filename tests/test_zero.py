"""ZeRO-1 sharded optimizer state + machine gossip (parallel/zero.py).

Ground truth: an unsharded replica-per-machine loop — grads averaged over
each machine's local batches, SGD+momentum in f32, then the machine
mixing matrix applied.  The sharded step must reproduce it exactly (up to
bf16 forward effects, which both sides share).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bluefog_tpu as bf
from bluefog_tpu import topology_util as tu
from bluefog_tpu.core import basics
from bluefog_tpu.parallel.zero import (
    make_zero_gossip_train_step,
    packed_layout,
    unpack_params,
)

MACHINES, LOCAL = 2, 4
LR, MOM = 0.05, 0.9


def _setup():
    bf.shutdown()
    bf.init(local_size=LOCAL)
    ctx = basics.context()
    assert ctx.hier_mesh.devices.shape == (MACHINES, LOCAL)
    bf.set_machine_topology(tu.RingGraph(MACHINES))
    return ctx


def _model():
    def apply_fn(params, x):
        h = jnp.tanh(x @ params["w1"])
        return h @ params["w2"]

    def loss_fn(pred, y):
        return jnp.mean((pred - y) ** 2)

    params = {
        "w1": jnp.asarray(np.random.default_rng(0).normal(size=(6, 5)),
                          jnp.float32) * 0.3,
        "w2": jnp.asarray(np.random.default_rng(1).normal(size=(5, 3)),
                          jnp.float32) * 0.3,
    }
    return apply_fn, loss_fn, params


def _data(rng):
    # [machines, local, B, 6] inputs / [machines, local, B, 3] targets
    x = rng.normal(size=(MACHINES, LOCAL, 4, 6)).astype(np.float32)
    y = rng.normal(size=(MACHINES, LOCAL, 4, 3)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def _reference_step(apply_fn, loss_fn, w_per_machine, mu, batch, labels, W):
    """Replica-per-machine ground truth in f32 packed space."""

    def machine_grad(wm, xm, ym):
        # mean over the machine's local batches (f32 compute, like the
        # sharded step under test)
        def loss_all(p):
            losses = [loss_fn(apply_fn(p, xm[l]), ym[l])
                      for l in range(LOCAL)]
            return sum(losses) / LOCAL

        return jax.grad(loss_all)(wm)

    new_w, new_mu = [], []
    for m in range(MACHINES):
        g = machine_grad(w_per_machine[m], batch[m], labels[m])
        mu_m = jax.tree_util.tree_map(lambda mu_, g_: MOM * mu_ + g_, mu[m], g)
        w_m = jax.tree_util.tree_map(
            lambda w_, mu_: w_ - LR * mu_, w_per_machine[m], mu_m)
        new_w.append(w_m)
        new_mu.append(mu_m)
    # machine mixing on the params
    mixed = []
    for m in range(MACHINES):
        mixed.append(jax.tree_util.tree_map(
            lambda *ws: sum(W[m, s] * ws[s] for s in range(MACHINES)), *new_w))
    return mixed, new_mu


def test_zero_gossip_matches_reference(devices):
    ctx = _setup()
    apply_fn, loss_fn, params = _model()
    init_fn, step_fn, params_of = make_zero_gossip_train_step(
        apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
        learning_rate=LR, momentum=MOM, compute_dtype=jnp.float32,
    )
    state = init_fn(params)
    rng = np.random.default_rng(7)
    W = tu.GetWeightMatrix(tu.RingGraph(MACHINES))

    ref_w = [params for _ in range(MACHINES)]
    ref_mu = [jax.tree_util.tree_map(jnp.zeros_like, params)
              for _ in range(MACHINES)]
    for i in range(5):
        batch, labels = _data(rng)
        state, loss = step_fn(state, batch, labels)
        assert np.isfinite(float(loss))
        ref_w, ref_mu = _reference_step(
            apply_fn, loss_fn, ref_w, ref_mu, batch, labels, W)

    # machine 0's replica must match the reference replica 0 exactly
    got = params_of(state)
    for k in ("w1", "w2"):
        np.testing.assert_allclose(
            np.asarray(got[k], dtype=np.float32),
            np.asarray(ref_w[0][k], dtype=np.float32),
            rtol=2e-5, atol=2e-5,
        )


def test_zero_state_is_sharded(devices):
    ctx = _setup()
    apply_fn, loss_fn, params = _model()
    init_fn, _, _ = make_zero_gossip_train_step(
        apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
        learning_rate=LR, momentum=MOM,
    )
    state = init_fn(params)
    layout = packed_layout(params, LOCAL)
    # each of the 8 devices must hold exactly ONE [1,1,shard] block —
    # the ZeRO partition, not a replica
    shard_len = layout.padded // LOCAL
    for s in state["master"].addressable_shards:
        assert s.data.shape == (1, 1, shard_len)
    assert state["master"].shape == (MACHINES, LOCAL, shard_len)


def test_unpack_roundtrip():
    params = {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.arange(5.0)}
    layout = packed_layout(params, 4)
    from bluefog_tpu.parallel.zero import _pack

    vec = _pack(jax.tree_util.tree_leaves(params), layout)
    assert vec.shape[0] % 4 == 0
    back = unpack_params(vec, layout, jnp.float32)
    for k in params:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]))


def test_fsdp_gossip_matches_reference(devices):
    """The GSPMD per-leaf variant must match the same replica-per-machine
    ground truth as the packed shard_map variant."""
    from bluefog_tpu.parallel.zero import make_fsdp_gossip_train_step

    ctx = _setup()
    apply_fn, loss_fn, params = _model()
    init_fn, step_fn, params_of = make_fsdp_gossip_train_step(
        apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
        learning_rate=LR, momentum=MOM, compute_dtype=jnp.float32,
    )
    state = init_fn(params)
    rng = np.random.default_rng(7)
    W = tu.GetWeightMatrix(tu.RingGraph(MACHINES))

    ref_w = [params for _ in range(MACHINES)]
    ref_mu = [jax.tree_util.tree_map(jnp.zeros_like, params)
              for _ in range(MACHINES)]
    for _ in range(5):
        batch, labels = _data(rng)
        # fsdp step takes [machines, per_machine_batch, ...]
        fb = batch.reshape(MACHINES, LOCAL * 4, 6)
        fl = labels.reshape(MACHINES, LOCAL * 4, 3)
        state, loss = step_fn(state, fb, fl)
        assert np.isfinite(float(loss))
        ref_w, ref_mu = _reference_step(
            apply_fn, loss_fn, ref_w, ref_mu, batch, labels, W)

    got = params_of(state)
    for k in ("w1", "w2"):
        np.testing.assert_allclose(
            np.asarray(got[k], dtype=np.float32),
            np.asarray(ref_w[0][k], dtype=np.float32),
            rtol=2e-5, atol=2e-5,
        )


def test_fsdp_bf16_momentum_tracks_f32(devices):
    """``momentum_dtype=bf16`` (the 8B memory config: f32-accumulate,
    bf16-store) must keep the bf16 state buffer and track the f32-momentum
    trajectory to bf16 resolution over several steps."""
    from bluefog_tpu.parallel.zero import make_fsdp_gossip_train_step

    ctx = _setup()
    apply_fn, loss_fn, params = _model()
    states, steps = [], []
    for mdt in (jnp.float32, jnp.bfloat16):
        init_fn, step_fn, params_of = make_fsdp_gossip_train_step(
            apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
            learning_rate=LR, momentum=MOM, compute_dtype=jnp.float32,
            momentum_dtype=mdt,
        )
        states.append(init_fn(params))
        steps.append((step_fn, params_of))
    (mu_bf,) = states[1]["opt"][:1]
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(mu_bf))
    rng = np.random.default_rng(11)
    for _ in range(4):
        batch, labels = _data(rng)
        fb = batch.reshape(MACHINES, LOCAL * 4, 6)
        fl = labels.reshape(MACHINES, LOCAL * 4, 3)
        for i, (step_fn, _) in enumerate(steps):
            states[i], loss = step_fn(states[i], fb, fl)
            assert np.isfinite(float(loss))
    got_f32 = steps[0][1](states[0])
    got_bf16 = steps[1][1](states[1])
    for k in ("w1", "w2"):
        np.testing.assert_allclose(
            np.asarray(got_bf16[k], np.float32),
            np.asarray(got_f32[k], np.float32), rtol=0, atol=2e-2)


def test_fsdp_adamw_nu_stays_f32_under_bf16_accumulators(devices):
    """adamw's second moment must be f32 REGARDLESS of momentum_dtype:
    its EMA decays by (1-b2) = 0.1%/step, below bf16's ~0.39% ulp — a
    bf16 nu can never decay and freezes at early-training values (r5
    code-review catch).  mu honors momentum_dtype; nu must not, and the
    dtypes must survive a step (no silent drift)."""
    from bluefog_tpu.parallel.zero import make_fsdp_gossip_train_step

    ctx = _setup()
    apply_fn, loss_fn, params = _model()
    init_fn, step_fn, _ = make_fsdp_gossip_train_step(
        apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
        learning_rate=LR, momentum=MOM, optimizer="adamw",
        compute_dtype=jnp.float32, momentum_dtype=jnp.bfloat16,
    )
    state = init_fn(params)
    mu, nu, count = state["opt"]
    for lf in jax.tree_util.tree_leaves(mu):
        assert lf.dtype == jnp.bfloat16
    for lf in jax.tree_util.tree_leaves(nu):
        assert lf.dtype == jnp.float32
    rng = np.random.default_rng(13)
    batch, labels = _data(rng)
    state, loss = step_fn(
        state, batch.reshape(MACHINES, LOCAL * 4, 6),
        labels.reshape(MACHINES, LOCAL * 4, 3))
    assert np.isfinite(float(loss))
    mu, nu, count = state["opt"]
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(mu))
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(nu))


def test_fsdp_state_is_sharded(devices):
    from bluefog_tpu.parallel.zero import make_fsdp_gossip_train_step

    ctx = _setup()
    apply_fn, loss_fn, params = _model()
    # pad leaf dims to multiples of LOCAL so every big leaf shards
    params = {
        "w1": jnp.zeros((8, 12), jnp.float32),
        "w2": jnp.zeros((12, 4), jnp.float32),
    }
    init_fn, _, _ = make_fsdp_gossip_train_step(
        lambda p, x: x @ p["w1"] @ p["w2"],
        lambda pred, y: jnp.mean((pred - y) ** 2),
        ctx.hier_mesh, ctx.machine_plan,
        learning_rate=LR, momentum=MOM,
    )
    state = init_fn(params)
    # w1 [machines, 8, 12]: dim 12 shards over LOCAL=4 -> per-device (1, 8, 3)
    for s in state["master"]["w1"].addressable_shards:
        assert s.data.shape == (1, 8, 3), s.data.shape


def _reference_step_adam(apply_fn, loss_fn, w_per_machine, opt_states,
                         batch, labels, W, opts):
    """Replica-per-machine ground truth with optax.adam (== the 'adamw'
    rule with wd=0: bias-corrected moments, eps outside the sqrt)."""
    new_w, new_s = [], []
    for m in range(MACHINES):
        def loss_all(p):
            losses = [loss_fn(apply_fn(p, batch[m][l]), labels[m][l])
                      for l in range(LOCAL)]
            return sum(losses) / LOCAL

        g = jax.grad(loss_all)(w_per_machine[m])
        upd, s = opts[m].update(g, opt_states[m], w_per_machine[m])
        import optax

        new_w.append(optax.apply_updates(w_per_machine[m], upd))
        new_s.append(s)
    mixed = [jax.tree_util.tree_map(
        lambda *ws: sum(W[m, s_] * ws[s_] for s_ in range(MACHINES)), *new_w)
        for m in range(MACHINES)]
    return mixed, new_s


@pytest.mark.parametrize("variant", ["packed", "fsdp"])
def test_zero_adamw_matches_optax_adam(devices, variant):
    import optax

    from bluefog_tpu.parallel.zero import (
        make_fsdp_gossip_train_step,
        make_zero_gossip_train_step,
    )

    ctx = _setup()
    apply_fn, loss_fn, params = _model()
    make = (make_zero_gossip_train_step if variant == "packed"
            else make_fsdp_gossip_train_step)
    init_fn, step_fn, params_of = make(
        apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
        learning_rate=LR, optimizer="adamw", compute_dtype=jnp.float32,
    )
    state = init_fn(params)
    rng = np.random.default_rng(3)
    W = tu.GetWeightMatrix(tu.RingGraph(MACHINES))

    opts = [optax.adam(LR) for _ in range(MACHINES)]
    ref_w = [params for _ in range(MACHINES)]
    ref_s = [opts[m].init(params) for m in range(MACHINES)]
    for _ in range(4):
        batch, labels = _data(rng)
        if variant == "packed":
            state, loss = step_fn(state, batch, labels)
        else:
            state, loss = step_fn(
                state, batch.reshape(MACHINES, LOCAL * 4, 6),
                labels.reshape(MACHINES, LOCAL * 4, 3))
        assert np.isfinite(float(loss))
        ref_w, ref_s = _reference_step_adam(
            apply_fn, loss_fn, ref_w, ref_s, batch, labels, W, opts)

    got = params_of(state)
    for k in ("w1", "w2"):
        np.testing.assert_allclose(
            np.asarray(got[k], dtype=np.float32),
            np.asarray(ref_w[0][k], dtype=np.float32),
            rtol=3e-5, atol=3e-5,
        )


def test_zero_adamw_weight_decay_matches_optax_adamw(devices):
    """weight_decay must be DECOUPLED (AdamW, not L2-in-grad): exact
    match vs optax.adamw at wd=0.01."""
    import optax

    ctx = _setup()
    apply_fn, loss_fn, params = _model()
    init_fn, step_fn, params_of = make_zero_gossip_train_step(
        apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
        learning_rate=LR, optimizer="adamw", weight_decay=0.01,
        compute_dtype=jnp.float32,
    )
    state = init_fn(params)
    rng = np.random.default_rng(5)
    W = tu.GetWeightMatrix(tu.RingGraph(MACHINES))
    opts = [optax.adamw(LR, weight_decay=0.01) for _ in range(MACHINES)]
    ref_w = [params for _ in range(MACHINES)]
    ref_s = [opts[m].init(params) for m in range(MACHINES)]
    for _ in range(3):
        batch, labels = _data(rng)
        state, _ = step_fn(state, batch, labels)
        ref_w, ref_s = _reference_step_adam(
            apply_fn, loss_fn, ref_w, ref_s, batch, labels, W, opts)
    got = params_of(state)
    for k in ("w1", "w2"):
        np.testing.assert_allclose(
            np.asarray(got[k], dtype=np.float32),
            np.asarray(ref_w[0][k], dtype=np.float32),
            rtol=3e-5, atol=3e-5,
        )


@pytest.mark.skip(
    reason="environmental SIGSEGV: restore_like onto fresh sharded placements "
    "crashes the forked XLA CPU client in this container (multiprocess-on-CPU "
    "teardown, not a product bug)"
)
def test_zero_state_checkpoint_resume(devices, tmp_path):
    """Exact resume of SHARDED state: save after 2 steps, restore onto
    fresh sharded placements (checkpoint.restore_like), continue 2 more —
    must equal an uninterrupted 4-step run bit-for-bit in f32."""
    from bluefog_tpu import checkpoint

    ctx = _setup()
    apply_fn, loss_fn, params = _model()

    def make():
        return make_zero_gossip_train_step(
            apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
            learning_rate=LR, optimizer="adamw", compute_dtype=jnp.float32,
        )

    data = []
    rng = np.random.default_rng(11)
    for _ in range(4):
        data.append(_data(rng))

    # uninterrupted
    init_fn, step_fn, params_of = make()
    state = init_fn(params)
    for b, l in data:
        state, _ = step_fn(state, b, l)
    want = params_of(state)

    # interrupted at step 2
    init_fn2, step_fn2, params_of2 = make()
    state2 = init_fn2(params)
    for b, l in data[:2]:
        state2, _ = step_fn2(state2, b, l)
    path = str(tmp_path / "zero_ckpt")
    checkpoint.save(path, state2)
    init_fn3, step_fn3, params_of3 = make()
    template = init_fn3(params)       # fresh sharded placements + layout
    state3 = checkpoint.restore_like(path, template)
    # restored leaves carry the ZeRO sharding, not replicas
    assert state3["master"].sharding == template["master"].sharding
    for b, l in data[2:]:
        state3, _ = step_fn3(state3, b, l)
    got = params_of3(state3)
    for k in ("w1", "w2"):
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


@pytest.mark.parametrize("variant", ["packed", "fsdp"])
def test_zero_single_machine_no_gossip(devices, variant):
    """machines=1 (flat ZeRO, no machine axis to gossip over — the common
    non-decentralized use): state shards over all 8 devices and the step
    matches plain data-parallel SGD+momentum."""
    from bluefog_tpu.parallel.zero import (
        make_fsdp_gossip_train_step,
        make_zero_gossip_train_step,
    )

    bf.shutdown()
    bf.init(local_size=8)
    ctx = basics.context()
    assert ctx.hier_mesh.devices.shape == (1, 8)
    apply_fn, loss_fn, params = _model()
    make = (make_zero_gossip_train_step if variant == "packed"
            else make_fsdp_gossip_train_step)
    init_fn, step_fn, params_of = make(
        apply_fn, loss_fn, ctx.hier_mesh, None,
        learning_rate=LR, momentum=MOM, compute_dtype=jnp.float32,
    )
    state = init_fn(params)
    rng = np.random.default_rng(9)
    x = rng.normal(size=(1, 8, 4, 6)).astype(np.float32)
    y = rng.normal(size=(1, 8, 4, 3)).astype(np.float32)

    # ground truth: single replica, grads averaged over all 8 batches
    def loss_all(p):
        return sum(loss_fn(apply_fn(p, jnp.asarray(x[0, l])),
                           jnp.asarray(y[0, l])) for l in range(8)) / 8

    g = jax.grad(loss_all)(params)
    ref = jax.tree_util.tree_map(lambda w, g_: w - LR * g_, params, g)

    if variant == "packed":
        state, loss = step_fn(state, jnp.asarray(x), jnp.asarray(y))
    else:
        state, loss = step_fn(
            state, jnp.asarray(x.reshape(1, 32, 6)),
            jnp.asarray(y.reshape(1, 32, 3)))
    assert np.isfinite(float(loss))
    got = params_of(state)
    for k in ("w1", "w2"):
        np.testing.assert_allclose(
            np.asarray(got[k], np.float32), np.asarray(ref[k], np.float32),
            rtol=2e-5, atol=2e-5)
