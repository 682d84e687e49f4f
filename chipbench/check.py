"""How `correct` is decided: the timed object's first three steps against the
configuration's plain reference.

The runner drives the compiled step it is about to time through three steps
from the seeded start and captures, per rank: each step's loss, the norm of
every leaf of the first gradient as the optimizer got it, every parameter
after step one (compared leaf by leaf, and as the size of its first change over
the whole tree), and the norm of every leaf's change after step three.  After
the window has closed and the program's state is freed, `reference_run`
follows the same three steps with the plain reference (jax.value_and_grad at
the stated precision, the optax update, the mixing in NumPy with the matrix
written from the topology's definition) and `compare` sets the numbers side
by side, each with a limit of its own from the reference file's LIMITS.

The controls (a step in float8, a payload rounded to bfloat16) are the same
`reference_run` with `lower_step` / `lower_payload`, put in the program's
place by chipbench/control.py and by the tests; a benchmark run never runs
them.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import optimizers, seeded

STEPS = 3


def _rank_norms(a):
    """[ranks, ...] -> the L2 norm of every rank's slice, in float32."""
    return jnp.sqrt(jnp.sum(
        jnp.square(a.astype(jnp.float32)).reshape(a.shape[0], -1), axis=1))


def _to_host(norms):
    return {p: np.asarray(v, np.float64) for p, v in norms.items()}


def leaf_norms(flat):
    """{path: [ranks, ...]} on the device -> {path: float64[ranks]}."""
    return _to_host(jax.jit(
        lambda t: {p: _rank_norms(a) for p, a in t.items()})(flat))


def delta_norms(after, before):
    """The same of after - before."""
    return _to_host(jax.jit(
        lambda a, b: {p: _rank_norms(a[p] - b[p]) for p in a})(after, before))


def _np_norms(flat):
    return {p: np.sqrt(np.sum(np.square(a.astype(np.float64)).reshape(
        a.shape[0], -1), axis=1)) for p, a in flat.items()}


def _round_bf16(a):
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


def mix(M, stack, lower_payload=False, payload_includes_self=False):
    """One gossip round of a [ranks, ...] leaf in float64: (M @ x) / (M @ 1).
    With `lower_payload` what travels (and, for a window that holds the
    rank's own share in the payload's type, what stays) is rounded to
    bfloat16 first."""
    x = stack.astype(np.float64)
    p = M @ np.ones(M.shape[0])
    if lower_payload:
        sent = _round_bf16(x)
        own = sent if payload_includes_self else x
        D = np.diag(np.diag(M))
        m = np.tensordot(D, own, axes=1) + np.tensordot(M - D, sent, axes=1)
    else:
        m = np.tensordot(M, x, axes=1)
    return m / p.reshape((-1,) + (1,) * (x.ndim - 1)), p


def local_step_fn(reference, sizes, opt_spec, lower_step=False):
    """One rank's step of the plain reference, jitted: loss and gradient at
    the stated precision (or one below), then the optax update."""
    tx = optimizers.make(opt_spec)

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def local_step(p, s, o, x, y):
        (loss, new_s), g = jax.value_and_grad(
            lambda p_: reference.loss_fn(p_, s, x, y, sizes, lower_step),
            has_aux=True)(p)
        updates, o = tx.update(g, o, p)
        return optax.apply_updates(p, updates), new_s, o, loss, g

    return local_step


def reference_run(reference, sizes, opt_spec, M, seed, batches, *,
                  lower_step=False, lower_payload=False,
                  payload_includes_self=False, local_step=None, weights=None):
    """Three steps of the plain reference from the seeded start.  `batches`
    is the benchmark's own seeded pool.  Returns the same captures the runner
    takes from the program.  `local_step` and `weights` let a caller that runs
    several seeds keep one compiled `local_step_fn` and one weights builder."""
    n = M.shape[0]
    params0, stats0 = weights or seeded.make_weights(reference, sizes, seed)
    tx = optimizers.make(opt_spec)
    local_step = local_step or local_step_fn(reference, sizes, opt_spec, lower_step)

    dev = jax.devices()[0]
    paths = list(params0)
    out = {"losses": np.zeros((STEPS, n)), "assoc_p": None,
           "params0": {path: np.asarray(params0[path])[None] for path in paths}}
    # the step donates its parameters and its optimizer state, so every rank
    # starts from a tree of its own and the device holds 16 bytes a parameter
    # (p, mu, nu, the gradient) beside the step's activations
    starts = [jax.tree_util.tree_map(jnp.copy, params0) for _ in range(n - 1)]
    ranks = [(p, stats0, tx.init(p)) for p in starts + [params0]]
    identity = n == 1 and not lower_payload
    grad_norms = []  # [ranks][leaves], of the first step
    for k in range(STEPS):
        x_all, y_all = batches[k]
        for r, (p, s, o) in enumerate(ranks):
            x = jax.device_put(x_all[r], dev)
            y = jax.device_put(y_all[r], dev)
            p, s, o, loss, g = local_step(p, s, o, x, y)
            out["losses"][k, r] = float(loss)
            if k == 0:
                grad_norms.append([
                    float(jnp.linalg.norm(g[path].astype(jnp.float32)))
                    for path in paths])
            del g  # 4 bytes a parameter that the next step needs
            ranks[r] = (p, s, o)
        if k == 0:
            out["grad_norms"] = dict(zip(paths, np.array(grad_norms).T))
            out["assoc_p"] = M @ np.ones(n)
        if identity and k > 0:
            continue  # (c x) / c is x: nothing to carry through the host
        mixed = {}
        for path in paths:
            stack = np.stack([np.asarray(p[path]) for p, _, _ in ranks])
            mixed[path] = mix(M, stack, lower_payload, payload_includes_self)[0] \
                .astype(np.float32)
        if k == 0:
            out["params1"] = mixed
        for r, (p, _, _) in enumerate(ranks):
            for path in paths:  # leaf by leaf: the old leaf goes as the new comes
                p[path] = jax.device_put(mixed[path][r], dev)
    end = {path: np.stack([np.asarray(p[path]) for p, _, _ in ranks])
           for path in paths}
    out["delta_norms"] = _np_norms({p: end[p] - out["params0"][p] for p in end})
    return out


def _worst_norm_gap(got, ref):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    paths = sorted(ref)
    r = np.stack([ref[p] for p in paths])      # [leaves, ranks]
    g = np.stack([got[p] for p in paths])
    floor = np.median(r, axis=0, keepdims=True)
    gaps = np.abs(g - r) / np.maximum(np.maximum(r, floor), 1e-30)
    i = np.unravel_index(np.argmax(gaps), gaps.shape)
    return float(gaps[i]), "/".join(paths[i[0]])


def _worst_rel_l2(got, ref):
    paths = sorted(ref)
    norms = _np_norms(ref)
    floor = np.median(np.stack([norms[p] for p in paths]), axis=0)
    worst, where = 0.0, ""
    for p in paths:
        d = got[p].astype(np.float64) - ref[p].astype(np.float64)
        err = np.sqrt(np.sum(np.square(d).reshape(d.shape[0], -1), axis=1))
        rel = float(np.max(err / np.maximum(np.maximum(norms[p], floor), 1e-30)))
        if rel > worst:
            worst, where = rel, "/".join(p)
    return worst, where


def _change_rel_l2(got, ref):
    """Over the whole tree, how far the size of each parameter's first change
    is from the reference's: ||abs(dp) - abs(dr)|| / ||dr||.  Sizes and not
    signed changes, because Adam's first step is +-lr by the gradient's sign
    and a sign flips on rounding where the gradient is all but zero; a payload
    rounded on its way moves the size of every element's change."""
    num = den = 0.0
    for p, start in ref["params0"].items():
        dp = np.abs(got["params1"][p].astype(np.float64) - start)
        dr = np.abs(ref["params1"][p].astype(np.float64) - start)
        num += float(np.sum(np.square(dp - dr)))
        den += float(np.sum(np.square(dr)))
    return (num / max(den, 1e-300)) ** 0.5


def compare(got, ref, limits):
    """Every number that the configuration's LIMITS name, beside its limit.
    Returns ({name: {"value", "limit", "ok", "where"}}, all ok)."""
    numbers = {}

    def put(name, value, where=""):
        if name not in limits:
            return
        if callable(value):
            value, where = value()
        ok = bool(np.isfinite(value) and value <= limits[name])
        numbers[name] = {"value": float(value), "limit": limits[name],
                         "ok": ok, "where": where}

    put("loss_gap", np.max(np.abs(got["losses"] - ref["losses"])))
    put("grad_norm_gap", lambda: _worst_norm_gap(got["grad_norms"], ref["grad_norms"]))
    put("delta_norm_gap", lambda: _worst_norm_gap(got["delta_norms"], ref["delta_norms"]))
    put("params1_rel_l2", lambda: _worst_rel_l2(got["params1"], ref["params1"]))
    put("change1_rel_l2", lambda: (_change_rel_l2(got, ref), "whole tree"))
    put("assoc_p_gap", np.max(np.abs(np.asarray(got["assoc_p"], np.float64)
                                     - ref["assoc_p"])))
    return numbers, all(v["ok"] for v in numbers.values())
