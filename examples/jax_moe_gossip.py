"""Mixture-of-experts x decentralized-gossip training: a (dp, ep) mesh where
each gossip replica's MoE layers shard their experts over the ``ep`` axis
(tokens dispatched by ``all_to_all``), and replicas neighbor-average ALL
parameters — expert shards mix shard-wise, exactly like tensor parallelism
(see examples/jax_tp_gossip.py; EP is absent upstream, SURVEY.md §2.3).

Layout rule (split_tp_params docstring): expert leaves enter shard_map
stacked [dp, ep, ...] / P("bf_nodes", "ep"); everything else (embed, attn,
router, norms, unembed) enters [dp, ...] / P("bf_nodes") — ep-INVARIANT.
Tokens are ep-sharded, so per-device losses are ep-varying; dividing the
local loss by the ep size makes every gradient exactly d(mean loss): the
auto-inserted pvary transpose psums replicated-leaf grads, and the
all_to_all transpose returns expert-grad contributions, both seeded once
per device.  Ground truth: an ep=N run matches ep=1 loss-for-loss.

Run (CPU mesh): JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/jax_moe_gossip.py --steps 30
"""

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu import ops_spmd
from bluefog_tpu import topology_util as tu
from bluefog_tpu.core.plan import compile_plan
from bluefog_tpu.models.transformer import dense_attention
from bluefog_tpu.parallel import expert as epx

VOCAB = 64


def init_params(key, d_model, heads, d_ff, n_experts, layers):
    ks = jax.random.split(key, 2 * layers + 2)
    dh = d_model // heads

    def dense(k, shape, fan):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan)

    repl = {
        "embed": dense(ks[0], (VOCAB, d_model), d_model) * 3.0,
        "unembed": dense(ks[-1], (d_model, VOCAB), d_model),
        "blocks": [],
    }
    experts = {"blocks": []}
    for i in range(layers):
        ka = jax.random.split(ks[1 + 2 * i], 5)
        moe = epx.init_moe_params(ks[2 + 2 * i], d_model, d_ff, n_experts)
        repl["blocks"].append({
            "wq": dense(ka[0], (d_model, heads, dh), d_model),
            "wk": dense(ka[1], (d_model, heads, dh), d_model),
            "wv": dense(ka[2], (d_model, heads, dh), d_model),
            "wo": dense(ka[3], (heads, dh, d_model), d_model),
            "norm1": jnp.ones((d_model,)),
            "norm2": jnp.ones((d_model,)),
            "router": moe["router"],
        })
        experts["blocks"].append({"wi": moe["wi"], "wo": moe["wo"]})
    return repl, experts


def rms(x, scale, eps=1e-6):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale


def forward(repl, experts, ids, ep_axis, capacity_factor):
    """ids [B_local, T] (this ep device's shard) -> (logits, mean aux)."""
    x = repl["embed"][ids]  # [B, T, d]
    auxes = []
    for blk, moe in zip(repl["blocks"], experts["blocks"]):
        h = rms(x, blk["norm1"])
        q = jnp.einsum("btm,mhd->bthd", h, blk["wq"])
        k = jnp.einsum("btm,mhd->bthd", h, blk["wk"])
        v = jnp.einsum("btm,mhd->bthd", h, blk["wv"])
        att = dense_attention(q, k, v, causal=True, dtype=x.dtype)
        x = x + jnp.einsum("bthd,hdm->btm", att, blk["wo"])
        h = rms(x, blk["norm2"])
        flat = h.reshape(-1, h.shape[-1])
        moe_in = {"router": blk["router"], "wi": moe["wi"], "wo": moe["wo"]}
        out, aux = epx.switch_moe(
            flat, moe_in, ep_axis, capacity_factor=capacity_factor
        )
        auxes.append(aux)
        x = x + out.reshape(x.shape)
    # every layer's router needs its load-balancing gradient
    return jnp.einsum("btm,mv->btv", x, repl["unembed"]), jnp.mean(
        jnp.stack(auxes)
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--ep", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--d-ff", type=int, default=64)
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8, help="sequences per replica")
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--capacity-factor", type=float, default=0.0,
                    help="0 = ample (no drops)")
    ap.add_argument("--aux-weight", type=float, default=0.01,
                    help="Switch load-balancing loss weight (a per-shard "
                         "statistic: ep>1 differs slightly from ep=1)")
    args = ap.parse_args()

    devices = jax.devices()
    need = args.dp * args.ep
    if len(devices) < need:
        raise SystemExit(
            f"need {need} devices (dp={args.dp} x ep={args.ep}), have "
            f"{len(devices)}"
        )
    if args.experts % args.ep or args.batch % args.ep:
        raise SystemExit("--experts and --batch must divide by --ep")
    cf = args.capacity_factor or float(args.experts)
    mesh = Mesh(np.array(devices[:need]).reshape(args.dp, args.ep),
                ("bf_nodes", "ep"))
    plan = compile_plan(tu.ExponentialTwoGraph(args.dp))

    per_repl, per_exp = [], []
    for r in range(args.dp):
        rp, ex = init_params(jax.random.PRNGKey(r), args.d_model, args.heads,
                             args.d_ff, args.experts, args.layers)
        per_repl.append(rp)
        per_exp.append(jax.tree_util.tree_map(
            lambda a: a.reshape((args.ep, a.shape[0] // args.ep) + a.shape[1:]),
            ex,
        ))
    stack = lambda *ls: jnp.stack(ls)
    repl = jax.tree_util.tree_map(stack, *per_repl)
    exp = jax.tree_util.tree_map(stack, *per_exp)
    opt = optax.sgd(args.lr, momentum=0.9)
    opt_r = jax.tree_util.tree_map(stack, *[opt.init(p) for p in per_repl])
    opt_e = jax.tree_util.tree_map(stack, *[opt.init(p) for p in per_exp])

    def loss_fn(repl_p, exp_p, ids):
        logits, aux = forward(repl_p, exp_p, ids[:, :-1], "ep", cf)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, ids[:, 1:]
        ).mean()
        # /ep: per-device losses are ep-varying; this seeding makes every
        # gradient exactly d(mean-over-mesh loss) (module docstring)
        return (ce + args.aux_weight * aux) / args.ep, ce

    def spmd_step(repl, exp, opt_r, opt_e, ids):
        t1 = functools.partial(jax.tree_util.tree_map, lambda a: a[0])
        t2 = functools.partial(jax.tree_util.tree_map, lambda a: a[0, 0])
        pr, pe, sr, se = t1(repl), t2(exp), t1(opt_r), t2(opt_e)
        (_, ce), (gr, ge) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True
        )(pr, pe, ids[0, 0])
        ur, sr = opt.update(gr, sr, pr)
        pr = optax.apply_updates(pr, ur)
        ue, se = opt.update(ge, se, pe)
        pe = optax.apply_updates(pe, ue)
        pr = ops_spmd.neighbor_allreduce(pr, plan, "bf_nodes")
        pe = ops_spmd.neighbor_allreduce(pe, plan, "bf_nodes")
        e1 = functools.partial(jax.tree_util.tree_map, lambda a: a[None])
        e2 = functools.partial(jax.tree_util.tree_map, lambda a: a[None, None])
        ce = jax.lax.pmean(jax.lax.pmean(ce, "ep"), "bf_nodes")[None, None]
        return e1(pr), e2(pe), e1(sr), e2(se), ce

    step = jax.jit(
        jax.shard_map(
            spmd_step, mesh=mesh,
            in_specs=(P("bf_nodes"), P("bf_nodes", "ep"), P("bf_nodes"),
                      P("bf_nodes", "ep"), P("bf_nodes", "ep")),
            out_specs=(P("bf_nodes"), P("bf_nodes", "ep"), P("bf_nodes"),
                       P("bf_nodes", "ep"), P("bf_nodes", "ep")),
            # the replicated-leaf states ARE ep-invariant (the /ep loss
            # seeding makes every grad the mean-over-mesh grad — module
            # docstring), but the replication checker cannot infer that
            # through the optax momentum update, so tell it to trust us
            check_vma=False,
        )
    )

    rng = np.random.default_rng(0)

    def batch():
        # learnable synthetic language: token' = token + 1 mod VOCAB
        start = rng.integers(0, VOCAB, size=(args.dp, args.batch, 1))
        ids = (start + np.arange(args.seq + 1)) % VOCAB
        return jnp.asarray(ids, jnp.int32).reshape(
            args.dp, args.ep, args.batch // args.ep, args.seq + 1
        )

    for i in range(args.steps):
        repl, exp, opt_r, opt_e, loss = step(repl, exp, opt_r, opt_e, batch())
        if (i + 1) % 10 == 0 or i == 0:
            w = np.asarray(exp["blocks"][0]["wi"])
            spread = float(np.abs(w - w.mean(axis=0, keepdims=True)).max())
            print(
                f"step {i + 1:3d}: loss {float(np.asarray(loss).mean()):.4f} "
                f"consensus-spread {spread:.2e}"
            )

    print(f"done: dp={args.dp} ep={args.ep} on {need} devices")


if __name__ == "__main__":
    main()
