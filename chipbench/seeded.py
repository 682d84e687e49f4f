"""Weights and batches from --seed: made on the device, in one jitted call
each, in the type they are trained in.  The shapes and the rule for each leaf
come from the configuration's plain reference, not from the program."""

import zlib

import jax
import jax.numpy as jnp


def key_of(seed):
    """A PRNG key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def nest(flat):
    """{('a', 'b'): v} -> {'a': {'b': v}}, the layout the program's model takes."""
    out = {}
    for path, v in flat.items():
        d = out
        for name in path[:-1]:
            d = d.setdefault(name, {})
        d[path[-1]] = v
    return out


def flatten(tree, prefix=()):
    """The inverse of `nest`."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def weights_builder(reference, sizes, ranks=None, sharding=None):
    """A jitted `build(key) -> (params, batch statistics)`, flat {path: array}
    dicts.  With `ranks` every leaf gets a leading rank axis (every rank
    starts from the same weights) and is created under `sharding`."""
    p_shapes, s_shapes = reference.param_shapes(sizes)

    def build(key):
        def leaf(path, shape):
            kind, value = reference.init_rule(path, shape)
            if kind == "normal":
                k = jax.random.fold_in(
                    key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)
                a = value * jax.random.normal(k, shape, jnp.float32)
            else:
                a = jnp.full(shape, value, jnp.float32)
            return a if ranks is None else jnp.broadcast_to(a[None], (ranks,) + shape)
        return ({p: leaf(p, s) for p, s in p_shapes.items()},
                {p: leaf(p, s) for p, s in s_shapes.items()})

    return jax.jit(build, out_shardings=sharding)


def make_weights(reference, sizes, seed, ranks=None, sharding=None):
    return weights_builder(reference, sizes, ranks, sharding)(key_of(seed))


def make_batches(reference, sizes, seed, ranks, pool, sharding=None):
    """`pool` distinct batches, each (x, y) with shape [ranks, per_rank_batch,
    ...]: every row differs from every other."""
    spec = reference.input_shapes(sizes)
    b = sizes["per_rank_batch"]

    def draw(key, shape, dtype, high):
        if high is None:
            return jax.random.normal(key, shape, jnp.dtype(dtype))
        return jax.random.randint(key, shape, 0, high, jnp.dtype(dtype))

    def build(key):
        out = []
        for i in range(pool):
            kx, ky = jax.random.split(jax.random.fold_in(key, 1000 + i))
            out.append(tuple(
                draw(k, (ranks, b) + tuple(shape), dtype, high)
                for k, (shape, dtype, high) in ((kx, spec["x"]), (ky, spec["y"]))))
        return out

    return jax.jit(build, out_shardings=sharding)(key_of(seed))
