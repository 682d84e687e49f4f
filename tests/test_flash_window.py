"""The causal band in the flash-attention kernels: ``window=`` in the forward
kernel and both backward kernels (Pallas, interpret mode at tiny blocks) and
in the XLA fall-back against the masked dense attention, at a window smaller
than, equal to and larger than the sequence; ``window=None`` against the call
that never heard of a window, bit for bit.  And the tiles that a band's edge
or the causal diagonal cuts, walked in sub-tiles by the backward kernels (PR
36): their builder through its internal edge at blocks the CPU can interpret,
the text a small block lowers to, and the gauges that count the pairs."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu import telemetry
from bluefog_tpu.kernels.flash_attention import (
    _Band, _flash_bwd_pallas, _flash_fwd, _sub_edge, flash_attention,
    flash_attention_with_lse, make_flash_attention_fn)

B, T, H, D = 1, 64, 2, 16


def masked_dense(q, k, v, window, q_start=0, k_start=0):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i = q_start + jnp.arange(q.shape[1])[:, None]
    j = k_start + jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", jnp.where(seen, p, 0.0), v)


@pytest.fixture(scope="module")
def qkvg():
    return tuple(jax.random.normal(k, (B, T, H, D))
                 for k in jax.random.split(jax.random.PRNGKey(0), 4))


# smaller than a block, across blocks, the sequence itself, past it
WINDOWS = [1, 5, 24, T, T + 36]


@pytest.mark.parametrize("impl,blocks", [
    ("pallas", (8, 8)), ("pallas", (16, 8)), ("pallas", (8, 16)), ("xla", (8, 8))])
@pytest.mark.parametrize("window", WINDOWS)
def test_band_forward_and_both_backward_kernels_match_masked_dense(
        qkvg, impl, blocks, window):
    q, k, v, g = qkvg
    kw = dict(window=window, block_q=blocks[0], block_k=blocks[1], impl=impl)
    out = flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(out, masked_dense(q, k, v, window), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a, **kw) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(masked_dense(*a, window) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):  # dQ, then dK and dV (the other kernel)
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_the_band_grid_visits_only_the_blocks_the_band_touches():
    band = _Band(1024, 1024, 4096, 8, 8)  # the benchmark's window layer
    assert (band.k_steps, band.q_steps) == (5, 5)
    tiles = sum(band.k_hi(i) - band.k_lo(i) + 1 for i in range(8))
    assert tiles == 30  # a causal kernel runs 36, a masking one visits 64
    assert [band.k_lo(i) for i in range(8)] == [0, 0, 0, 0, 0, 1, 2, 3]
    assert [band.q_hi(j) for j in range(8)] == [4, 5, 6, 7, 7, 7, 7, 7]
    wide = _Band(1024, 1024, 8192, 8, 8)  # a window as long as the sequence
    assert sum(wide.k_hi(i) - wide.k_lo(i) + 1 for i in range(8)) == 36


def test_band_with_traced_offsets_masks_on_global_positions(qkvg):
    """One hop of a ring with a window: the offsets are traced, so the
    dynamic-offset kernels mask (and skip) on global positions."""
    q, k, v, g = qkvg

    def hop(q, k, v, qs, ks):
        o, lse = flash_attention_with_lse(q, k, v, q_start=qs, k_start=ks,
                                          window=20, block_q=8, block_k=8)
        return jnp.sum(o * g)

    qs, ks = jnp.int32(64), jnp.int32(48)
    got = jax.grad(hop, (0, 1, 2))(q, k, v, qs, ks)
    want = jax.grad(lambda *a: jnp.sum(masked_dense(*a, 20, 64, 48) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_window_none_is_the_call_that_never_heard_of_a_window(qkvg):
    q, k, v, g = qkvg

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * g)

    old = loss(lambda *a: flash_attention(*a, causal=True, block_q=16, block_k=16))
    new = loss(lambda *a: flash_attention(*a, causal=True, block_q=16, block_k=16,
                                          window=None))
    made = loss(make_flash_attention_fn(block_q=16, block_k=16))
    texts = {hashlib.sha256(jax.jit(jax.grad(f, (0, 1, 2))).lower(q, k, v)
                            .as_text().encode()).hexdigest()
             for f in (old, new, made)}
    assert len(texts) == 1
    for a, b in zip(jax.grad(old, (0, 1, 2))(q, k, v), jax.grad(new, (0, 1, 2))(q, k, v)):
        assert np.array_equal(np.asarray(a), np.asarray(b))  # bit for bit


def test_a_window_needs_a_causal_mask():
    x = jnp.zeros((1, 8, 1, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        flash_attention(x, x, x, window=0)


# ---- cut tiles walked in sub-tiles (PR 36) --------------------------------


def _folded(x):  # [B, T, H, D] -> [B * H, T, D], as the builders take them
    return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])


# None: the causal diagonal alone; 16: a block's own width
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("edge", [8, 4], ids=["2x2", "4x4"])
@pytest.mark.parametrize("window", [None] + WINDOWS + [16])
def test_sub_tiled_backward_kernels_match_masked_dense(qkvg, window, edge, group):
    """16 x 16 blocks whose cut tiles the backward kernels walk in sub-tiles
    of ``edge``: dK/dV (summed over a group of query heads that share a
    key-value head) and dQ against the masked dense attention, after the
    forward, which computes its tiles whole."""
    q, k, v, g = qkvg
    k, v = k[:, :, :H // group], v[:, :, :H // group]
    kw = dict(scale=1 / math.sqrt(D), causal=True, block_q=16, block_k=16,
              interpret=True, tri_delta=0, window=window)
    out, lse = _flash_fwd(_folded(q), _folded(k), _folded(v), 0, 0, **kw)
    corr = -jnp.sum(out * _folded(g), axis=-1)  # the lse has no cotangent
    got = (out,) + _flash_bwd_pallas(
        _folded(q), _folded(k), _folded(v), lse, corr, 0, 0, _folded(g),
        sub=edge, **kw)

    def dense(q, k, v):
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        return masked_dense(q, k, v, window)

    want = (dense(q, k, v),) + jax.grad(
        lambda *a: jnp.sum(dense(*a) * g), (0, 1, 2))(q, k, v)
    assert got[2].shape == _folded(k).shape  # dK left summed over the group
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, _folded(b), atol=5e-6)


@pytest.mark.parametrize("edge", [8, 4], ids=["2x2", "4x4"])
def test_sub_tiles_on_the_striped_rings_strict_diagonal_match_the_whole_tile(
        qkvg, edge):
    """Keys one position ahead (``tri_delta=1``, a striped ring's hop): the
    diagonal tile's first row sees none of its keys, the sentinel's case."""
    q, k, v, g = (_folded(x) for x in qkvg)
    kw = dict(scale=1 / math.sqrt(D), causal=True, block_q=16, block_k=16,
              interpret=True, tri_delta=1)

    out, lse = _flash_fwd(q, k, v, 0, 1, **kw)
    assert float(jnp.abs(out[:, 0]).max()) == 0.0  # row 0 saw nothing
    corr = -jnp.sum(out * g, axis=-1)
    got, want = (_flash_bwd_pallas(q, k, v, lse, corr, 0, 1, g, sub=sub, **kw)
                 for sub in (edge, 0))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_an_edge_is_chosen_from_the_blocks_and_from_nothing_else():
    assert _sub_edge(1024, 1024) == _sub_edge(2048, 1024) == 512
    assert _sub_edge(512, 512) is None and _sub_edge(1024, 512) is None
    assert _sub_edge(1280, 1280) is None  # 512 does not divide it


def _grad_text(fn, *args):
    return hashlib.sha256(jax.jit(jax.grad(fn, (0, 1, 2))).lower(*args)
                          .as_text().encode()).hexdigest()


# sha256 of the lowered gradient on the parent's tree (84e0f02), from the
# calls below run there: blocks under 1024 with static offsets, banded and
# whole-sequence, and 1024 x 1024 blocks with traced offsets (a ring's hop).
# Taken again at PR 40, whose names on the forward rule's output and logsumexp
# lower to nothing but move the number MLIR's symbol table gives two private
# functions (`@_where_72` -> `_73`, `@floor_divide_76` -> `_77`): with
# `@name_<n>` written `@name_N` the texts of 6f0fa38 and of PR 40 are equal,
# character for character, in all four
@pytest.mark.parametrize("block,window,hop,want", [
    pytest.param(16, 24, False, "290eaa2d17f7eb64ce4fe4b549d41210499a8ae08ca4739d19e923b84185e899", id="band-16"),
    pytest.param(512, 700, False, "e53a1d44e14a65ea4106fba2bcbfc45f5121b8cb304cc076612af0a5233e163d", id="band-512"),
    pytest.param(512, None, False, "8e36c7755df547ba504f14c1e08b0d09d9ef2ce36428e7fe42f8f9280010f39b", id="causal-512"),
    pytest.param(1024, 1500, True, "b82d4abd9168c3aa5050350cd5ebc1b79bdf8023a0e74dc5736c4f0810dbefb9", id="hop-1024"),
])
def test_small_blocks_and_traced_offsets_lower_to_the_parents_text(
        block, window, hop, want):
    T = 64 if block == 16 else 2048
    q = jax.ShapeDtypeStruct((1, T, 4, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, T, 2, 16), jnp.float32)
    kw = dict(window=window, block_q=block, block_k=block, interpret=True)
    if not hop:
        assert _grad_text(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, **kw)), q, kv, kv) == want
        return
    at = jax.ShapeDtypeStruct((), jnp.int32)

    def hop(q, k, v, qs, ks):  # the name is in the text
        o, lse = flash_attention_with_lse(q, k, v, q_start=qs, k_start=ks, **kw)
        return jnp.sum(o) + jnp.sum(lse)

    assert _grad_text(hop, kv, kv, kv, at, at) == want


# a head and a sequence of 8,192 at the decoder cells' attention shapes:
# (window, block) -> pairs visible, computed in whole tiles, by a backward kernel
PAIRS = {
    "smallthinker-window": (4096, None, 25_167_872, 31_457_280, 28_311_552),
    "laguna-window": (512, 512, 4_063_488, 8_126_464, 8_126_464),
    "global": (None, None, 33_558_528, 37_748_736, 35_651_584),
}


@pytest.mark.parametrize("name", sorted(PAIRS))
def test_gauges_count_the_pairs_seen_and_the_pairs_computed(name, monkeypatch,
                                                            tmp_path):
    window, block, visible, whole, computed = PAIRS[name]
    kind = "global" if window is None else "window"
    edge = block or 1024
    band = _Band(edge, edge, window or 8192, 8192 // edge, 8192 // edge)
    assert band.pairs(0) == (visible, whole)
    assert band.pairs(_sub_edge(edge, edge)) == (visible, computed)
    if block is None:  # what sub-tiles of 256 would leave, were they worth walking
        assert band.pairs(256)[1] == {"window": 26_738_688, "global": 34_603_008}[kind]
    monkeypatch.setenv("BFTPU_TELEMETRY", str(tmp_path))
    telemetry.reset()
    try:
        x = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)
        jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window, block_q=block, block_k=block,
            interpret=True).astype(jnp.float32)), (0, 1, 2)), x, x, x)
        gauges = {g["name"]: g["value"] for g in
                  telemetry.get_registry().snapshot()["gauges"]}
    finally:
        telemetry.reset()
    assert gauges == {f"attention.pairs_visible_{kind}": visible,
                      f"attention.pairs_computed_{kind}": computed}
