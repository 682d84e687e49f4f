"""Job kind `eager_window_pushsum`: the API-faithful push-sum round, copied
from benchmarks/bert_pushsum.build_flows -> eager_step (sound), with ids,
labels, parameters and optimizer state placed under
basics.rank_major_sharding.  Every rank: vmapped grad, optimizer update,
pack, win_accumulate to the ring successor, win_update with reset, debias by
win_associated_p, win_set_exposed, unpack."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

import bluefog_tpu as bf

from chipbench import optimizers, seeded

WINDOW = "chipbench_packed"
# what the trace shows of this job: the round starts with the grad program;
# the window programs are matched by today's names (PERF.md asks the tracing
# issue for stable ones)
STEP_ANCHOR = r"^jit_rank_loss"
WINDOW_PROGRAMS = (r"^jit_pack", r"^jit_unpack", r"^jit__combine", r"^jit_spmd",
                   r"^jit_broadcast_in_dim", r"^jit_true_divide",
                   r"^jit_convert_element_type", r"^jit__exchange",
                   r"^jit_put_update")


class Job:
    def __init__(self, spec):
        self.spec = spec
        ctx, mix = spec.ctx, spec.mix
        n = self.n = ctx.size
        bf.turn_on_win_ops_with_associated_p()
        apply_fn = spec.program["apply_fn"]
        params = seeded.nest(spec.params)
        flat0, self.treedef = jax.tree_util.tree_flatten(params)
        shapes = [a.shape[1:] for a in flat0]
        sizes = [int(np.prod(s, dtype=np.int64)) for s in shapes]

        @jax.jit
        def pack(flat):
            return jnp.concatenate([a.reshape(n, -1) for a in flat], axis=1)

        @jax.jit
        def unpack(packed):
            out, off = [], 0
            for s, sz in zip(shapes, sizes):
                out.append(packed[:, off:off + sz].reshape((n,) + s))
                off += sz
            return out

        self.pack, self.unpack = pack, unpack
        bf.win_create(pack(flat0), WINDOW, zero_init=True)
        opt = optimizers.make(spec.opt_spec)

        def rank_loss(p, x, y):
            logits = apply_fn({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        self.grad_fn = jax.jit(jax.vmap(jax.value_and_grad(rank_loss)))
        self.upd_fn = jax.jit(opt.update)
        self.apply_fn = jax.jit(optax.apply_updates)
        keep, off = mix["pushsum"]["keep"], mix["pushsum"]["successor_offset"]
        self.keep = keep
        self.dst = [{(r + off) % n: 1.0 - keep} for r in range(n)]
        self.ones_prev = [{(r - off) % n: 1.0} for r in range(n)]
        self.state = (params, opt.init(params))
        self.p_assoc = None

    def placement(self):
        params, opt_state = self.state
        return (params, [a for a in jax.tree_util.tree_leaves(opt_state)
                         if a.ndim >= 1])

    def step(self, k):
        spans, n = self.spec.spans, self.n
        with spans.span("input"):
            ids, labels = self.spec.batches[k % len(self.spec.batches)]
        params, opt_state = self.state
        loss, grads = self.grad_fn(params, ids, labels)
        updates, opt_state = self.upd_fn(grads, opt_state, params)
        params = self.apply_fn(params, updates)
        packed = self.pack(jax.tree_util.tree_flatten(params)[0])
        with spans.span("window_op"):
            bf.win_accumulate(packed, WINDOW, dst_weights=self.dst)
            m = bf.win_update(WINDOW, self_weight=self.keep,
                              neighbor_weights=self.ones_prev, reset=True)
            self.p_assoc = bf.win_associated_p(WINDOW)
            merged = m / self.p_assoc.reshape((n, 1)).astype(m.dtype)
            bf.win_set_exposed(WINDOW, merged, associated_p=1.0)
        leaves = self.unpack(merged)
        self.state = (jax.tree_util.tree_unflatten(self.treedef, leaves), opt_state)
        return leaves[-1], loss

    def params(self):
        return seeded.flatten(self.state[0])

    def first_gradient(self):
        return seeded.flatten(
            optimizers.first_gradient(self.spec.opt_spec, self.state[1]))

    def assoc_p(self):
        return np.asarray(self.p_assoc, np.float64)

    def structure(self):
        """After a round the window's associated weight is back at 1."""
        p = np.asarray(bf.win_associated_p(WINDOW), np.float64)
        return {"associated_p_after_round": p.tolist(),
                "ok": bool(np.all(p == 1.0))}

    def close(self):
        self.state = None
        bf.win_free(WINDOW)
        bf.turn_off_win_ops_with_associated_p()
