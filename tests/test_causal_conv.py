"""The causal convolution's kernels (`kernels/causal_conv.py`) in interpret mode
against the definition, `jax.nn.silu(hybrid.causal_conv(...))`, and `jax.grad`
of it: values and the three gradients over both input types, one block, several
and a last block that is not full; through the mixer; what a token may see, in
both directions; sequences of a batch apart; the input read in place; the shapes
that go the expression's way."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.kernels import causal_conv as cc
from bluefog_tpu.models import hybrid

BLOCK_T = 16  # tokens a step in these tests: the shapes stay small


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The kernels' token block read while they are traced: 16 here, and the
    traces made under another are dropped on both sides."""
    def drop():
        cc._fwd.clear_cache()
        cc._bwd.clear_cache()

    monkeypatch.setattr(cc, "_BLOCK_T", BLOCK_T)
    drop()
    yield
    drop()


def _expression(x, taps, bias, offset=0):
    c = taps.shape[1]
    return jax.nn.silu(hybrid.causal_conv(
        x[..., offset:offset + c], taps, bias)).astype(x.dtype)


def _inputs(dtype, batch, tokens, channels, wider=0, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (batch, tokens, channels + wider)).astype(dtype),
            jax.random.normal(k[1], (4, channels)), jax.random.normal(k[2], (channels,)),
            jax.random.normal(k[3], (batch, tokens, channels)).astype(dtype))


def _values(fn, x, taps, bias, weight):
    def loss(*a):
        y = fn(*a)
        return jnp.sum((y * weight).astype(jnp.float32)), y
    (_, y), grads = jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
        x, taps, bias)
    return dict(zip(("y", "dx", "dtaps", "dbias"), (y,) + grads))


def _one_ulp(want):
    """A unit in the last place of each bfloat16 of `want`, as float32."""
    mag = np.abs(np.asarray(want, np.float32))
    return 2.0 ** (np.floor(np.log2(np.maximum(mag, 1e-30))) - 7)


# one block, several blocks, a last block of 8 rows of 16; 128 and 384 channels
SHAPES = {"one-block": (1, 16, 128), "several-blocks": (2, 48, 128),
          "a-last-block-not-full": (2, 40, 384), "one-sequence-384": (1, 64, 384)}


@functools.lru_cache(maxsize=None)
def _both(dtype_name, shape):
    args = _inputs(jnp.dtype(dtype_name), *SHAPES[shape])
    return _values(cc.causal_conv_silu, *args), _values(_expression, *args)


@pytest.mark.parametrize("what", ["y", "dx", "dtaps", "dbias"])
@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_are_the_expression_and_its_gradient(dtype, shape, what):
    """float32 inputs: 1e-5 (sums in another order); bfloat16 outputs and dx:
    one unit in the last place of the expression's; the taps' and the bias's
    gradients are float32 sums over tokens and batch either way."""
    got, want = (np.asarray(v[what], np.float32) for v in _both(dtype, shape))
    assert got.shape == want.shape
    if dtype == "bfloat16" and what in ("y", "dx"):
        assert _both(dtype, shape)[0][what].dtype == jnp.bfloat16
        assert np.all(np.abs(got - want) <= _one_ulp(want))
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 1e-2)])
def test_the_gradients_through_the_mixer_are_the_expressions(monkeypatch, dtype, tol):
    """A Mamba-2 mixer whose shapes tile (128 channels of x, 64 each of B and
    C): its output and the gradient in its input and in every parameter, the
    convolution through the kernels against the same mixer sent down the
    expression.  Relative L2 a leaf."""
    dtype = jnp.dtype(dtype)
    mixer = hybrid.Mamba2Mixer(num_heads=2, head_dim=64, state_size=64, chunk=16,
                               dtype=dtype)
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    u = jax.random.normal(k[0], (2, 32, 32)).astype(dtype)
    weight = jax.random.normal(k[1], u.shape)
    params = mixer.init(k[2], u)
    params = jax.tree_util.tree_map(  # a bias that is not zero, taps that matter
        lambda a: a + 0.3 * jax.random.normal(k[2], a.shape, a.dtype), params)

    def run():
        def loss(p, u):
            out = mixer.apply(p, u)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        jaxpr = jax.make_jaxpr(jax.grad(lambda p, u: loss(p, u)[0], (0, 1)))(params, u)
        (_, out), grads = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(params, u)
        return {"out": out, "grads": grads}, str(jaxpr).count("causal_conv_")

    got, calls = run()
    assert calls >= 4  # x, and B with C: forward and backward
    monkeypatch.setattr(hybrid, "conv_kernels_take", lambda *a: False)
    want, calls = run()
    assert calls == 0
    leaves = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(leaves(got), leaves(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b), jax.tree_util.keystr(path)


def test_the_first_three_tokens_see_zeros_before_them():
    x, taps, bias, _ = _inputs(jnp.float32, 2, 32, 128)
    y = np.asarray(cc.causal_conv_silu(x, taps, bias))
    x, taps, bias = (np.asarray(a, np.float64) for a in (x, taps, bias))
    silu = lambda v: v / (1.0 + np.exp(-v))
    for t in range(3):
        pre = bias + sum(taps[3 - s] * x[:, t - s] for s in range(t + 1))
        np.testing.assert_allclose(y[:, t], silu(pre), atol=1e-5)


# a block's first row, its last, and rows inside one
@pytest.mark.parametrize("t0", [16, 31, 5, 29])
def test_a_token_sees_nothing_after_it_and_dx_nothing_before(t0):
    x, taps, bias, dy = _inputs(jnp.float32, 2, 48, 128)
    fn = lambda x: cc.causal_conv_silu(x, taps, bias)
    y, vjp = jax.vjp(fn, x)
    later = x.at[:, t0:].set(7.0)
    np.testing.assert_array_equal(fn(later)[:, :t0], y[:, :t0])
    assert not np.array_equal(fn(later)[:, t0], y[:, t0])
    # dx[t] is made of dpre[t .. t + 3]: the cotangent of earlier tokens is not in it
    earlier = dy.at[:, :t0].set(-3.0)
    np.testing.assert_array_equal(vjp(earlier)[0][:, t0:], vjp(dy)[0][:, t0:])
    assert not np.array_equal(vjp(earlier)[0][:, t0 - 1], vjp(dy)[0][:, t0 - 1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_sequences_of_a_batch_do_not_see_each_other(dtype):
    """What a block carries to the next (the rows before it forward, the rows
    after it backward) starts from zero at each sequence: a batch of two is
    its sequences one at a time, in values and in dx, whatever the other
    sequence ends or starts with."""
    x, taps, bias, dy = _inputs(jnp.dtype(dtype), 2, 40, 128)
    x = x.at[0, -3:].set(50.0).at[1, :3].set(-50.0)
    fn = lambda x: cc.causal_conv_silu(x, taps, bias)
    y, vjp = jax.vjp(fn, x)
    for b in range(2):
        alone, vjp_alone = jax.vjp(fn, x[b:b + 1])
        np.testing.assert_array_equal(y[b:b + 1], alone)
        np.testing.assert_array_equal(vjp(dy)[0][b:b + 1], vjp_alone(dy[b:b + 1])[0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_channels_inside_a_wider_array_are_read_where_they_lie(dtype):
    """`offset`: the channels 128 .. 384 of an array of 448, as the mixer's
    convolution reads its own out of [z, xBC, dt]: the slice's values, and a
    gradient that is zero outside them."""
    dtype = jnp.dtype(dtype)
    x, taps, bias, dy = _inputs(dtype, 2, 40, 256, wider=192)
    got = _values(lambda *a: cc.causal_conv_silu(*a, offset=128), x, taps, bias, dy)
    want = _values(lambda *a: _expression(*a, offset=128), x, taps, bias, dy)
    for name in want:
        a, b = (np.asarray(v[name], np.float32) for v in (got, want))
        assert a.shape == b.shape
        if dtype == jnp.bfloat16 and name in ("y", "dx"):
            assert np.all(np.abs(a - b) <= _one_ulp(b)), name
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)
    assert not np.any(np.asarray(got["dx"][..., :128], np.float32))
    assert not np.any(np.asarray(got["dx"][..., 384:], np.float32))


def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    _pallas_calls(getattr(sub, "jaxpr", sub), found)
    return found


# 192 and 130 channels are no whole 128-lane blocks, 12 tokens no whole tiles
@pytest.mark.parametrize("tokens,inner,states,kernel_calls", [
    (16, 128, 64, 2), (16, 128, 32, 0), (16, 130, 64, 0), (12, 128, 64, 0)])
def test_the_shapes_alone_choose_the_path(tokens, inner, states, kernel_calls):
    """`hybrid.conv_silu` over [z, xBC, dt]: two kernel calls where x's
    channels and B's with C's are whole blocks and the tokens whole tiles,
    else none, and the expression's values either way."""
    conv = inner + 2 * states
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    zxbcdt = jax.random.normal(k[0], (2, tokens, inner + conv + 2))
    taps, bias = jax.random.normal(k[1], (4, conv)), jax.random.normal(k[2], (conv,))
    assert hybrid.conv_kernels_take(tokens, inner, states, 4) == bool(kernel_calls)
    fn = lambda z: jnp.concatenate(hybrid.conv_silu(z, taps, bias, inner), axis=-1)
    found = _pallas_calls(jax.make_jaxpr(fn)(zxbcdt).jaxpr, collections.Counter())
    assert found == ({"causal_conv_fwd": kernel_calls} if kernel_calls else {})
    np.testing.assert_allclose(fn(zxbcdt), _expression(zxbcdt, taps, bias, offset=inner),
                               atol=1e-5)


def test_shapes_the_kernels_do_not_tile_are_refused_by_name():
    x, taps, bias, _ = _inputs(jnp.float32, 1, 16, 192)
    with pytest.raises(ValueError, match="128-lane"):
        cc.causal_conv_silu(x, taps, bias)
    assert not cc.tiles(16, 128, 8)  # eight taps: no row left for the bias's sum
    assert cc.tiles(16, 128, 7) and not cc.tiles(16, 128, 4, offset=64)


def test_the_backward_pass_keeps_nothing_a_kernel_made():
    """The residuals of the `custom_vjp` are its three arguments: under a
    block's remat nothing new is kept, and `REMAT_KEEPS` is the four names
    it was."""
    x, taps, bias, dy = _inputs(jnp.bfloat16, 1, 32, 128)
    _, res = cc._core_fwd(x, taps, bias, 0, True)
    assert [r is a for r, a in zip(res, (x, taps, bias))] == [True] * 3
    assert hybrid.REMAT_KEEPS == ("attn_out", "attn_lse", "mixer_out", "mlp_gate_up")
    grad = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        cc.causal_conv_silu(x, taps, bias).astype(jnp.float32))))(x)
    assert _pallas_calls(grad.jaxpr, collections.Counter()) == {
        "causal_conv_fwd": 1, "causal_conv_bwd": 1}
