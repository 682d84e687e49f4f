"""Unit tests for bench.paired_slope — the estimator every benchmark's
published number now flows through (r4 second continuation).  Synthetic
region functions with a known per-call time and per-region constant; no
devices involved."""

import sys

import pytest

sys.path.insert(0, __import__("os").path.dirname(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__))))

from bench import paired_slope


def _region_fn(per_call, constant, stalls=None):
    """region(k) = constant + k*per_call (+ a scripted stall per call #)."""
    calls = {"n": 0}
    stalls = stalls or {}

    def region(k):
        i = calls["n"]
        calls["n"] += 1
        return constant + k * per_call + stalls.get(i, 0.0)

    return region


def test_recovers_slope_exactly_despite_constant():
    region = _region_fn(per_call=0.05, constant=10.0)
    t, fb = paired_slope(region, 10, "t", lambda: 0.001)
    assert t == pytest.approx(0.05)
    assert fb is False


def test_constant_can_dwarf_the_signal():
    # 300 ms constant vs 5 ms/call — the regime that broke RTT
    # subtraction: the slope must still be exact
    region = _region_fn(per_call=0.005, constant=0.3)
    t, fb = paired_slope(region, 20, "t", lambda: 0.25)
    assert t == pytest.approx(0.005)
    assert fb is False


def test_fallback_on_nonpositive_slope():
    # big region reads FASTER than small (a stall hit the small region
    # and nothing else) -> slope non-positive -> guarded RTT fallback
    region = _region_fn(per_call=0.01, constant=0.1, stalls={0: 5.0})
    t, fb = paired_slope(region, 10, "t", lambda: 0.0)
    assert fb is True
    # fallback = subtract_rtt(t_big, rt=0, iters) = (0.1 + 10*0.01)/10
    assert t == pytest.approx(0.02)


def test_repeats_survive_stall_in_small_region():
    # A stall in round 0's SMALL region deflates that round's paired
    # delta; the conservative two-statistic rule must NOT cherry-pick it.
    # Rounds: (small0+stall, big0), (small1, big1), (small2, big2).
    region = _region_fn(per_call=0.05, constant=0.2, stalls={0: 0.2})
    t, fb = paired_slope(region, 10, "t", lambda: 0.0, repeats=3)
    assert fb is False
    # round 0's delta: (0.2+10*.05) - (0.2+5*.05+0.2) = 0.05 -> 0.01/call
    # (deflated); clean rounds give exactly 0.05/call; min-min also gives
    # 0.05.  Conservative max picks 0.05.
    assert t == pytest.approx(0.05)


def test_repeats_survive_stall_in_big_region():
    # A stall in one BIG region inflates that round's delta; min over
    # positive paired deltas ignores it, and min(t_bigs) skips the
    # stalled big region.
    region = _region_fn(per_call=0.05, constant=0.2, stalls={1: 0.7})
    t, fb = paired_slope(region, 10, "t", lambda: 0.0, repeats=3)
    assert fb is False
    assert t == pytest.approx(0.05)


def test_repeats_all_nonpositive_falls_back():
    region = _region_fn(per_call=0.01, constant=0.1,
                        stalls={0: 9.0, 2: 9.0, 4: 9.0})
    t, fb = paired_slope(region, 10, "t", lambda: 0.0, repeats=3)
    assert fb is True


def test_degenerate_iters_uses_fallback():
    region = _region_fn(per_call=0.05, constant=0.0)
    t, fb = paired_slope(region, 1, "t", lambda: 0.0)
    assert fb is True
    assert t == pytest.approx(0.05)


from bench import robust_min, throughput_range


def test_robust_min_reproduced_uses_min(capsys):
    """Top-2 within 3%: the true min stands."""
    assert robust_min([1.00, 1.02, 1.10]) == 1.00
    assert capsys.readouterr().err == ""


def test_robust_min_unreproduced_uses_second(capsys):
    """A stall-deflated outlier (r4 advisor: a stall in a pass's SMALL
    region deflates per-call and a plain min cherry-picks it) must not
    define the headline: the second smallest is reported."""
    assert robust_min([0.80, 1.00, 1.01], "t") == 1.00
    assert "not reproduced" in capsys.readouterr().err


def test_robust_min_single_pass():
    assert robust_min([1.5]) == 1.5


def test_throughput_range_orders_lo_hi():
    lo, hi = throughput_range([0.5, 0.4, 0.45], scale=100.0)
    assert lo == 200.0 and hi == 250.0 and lo <= hi


def test_bert_device_side_matches_eager(devices):
    """The BERT benchmark's device-side k-rounds program (the slope-timable
    headline) must implement EXACTLY the eager window-op round it stands
    in for: 3 push-sum rounds from identical state, params equal to f32
    tolerance on the 8-rank CPU ring."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bluefog_tpu as bf
    from benchmarks.bert_pushsum import PRESETS, build_flows

    bf.init()
    n = bf.size()
    (params, opt_state), eager_step, device_rounds, meta = build_flows(
        PRESETS["tiny"], n, seed=3)
    try:
        dstate, dloss = device_rounds(
            meta["device_init"](params, opt_state), 3)
        e_params, e_opt = params, opt_state
        for _ in range(3):
            e_params, e_opt, eloss = eager_step(e_params, e_opt)
        for a, b in zip(jax.tree_util.tree_leaves(dstate["params"]),
                        jax.tree_util.tree_leaves(e_params)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=2e-2)  # bf16 params: one ulp at unit scale is ~8e-3
        np.testing.assert_allclose(
            float(np.asarray(dloss).mean()), float(np.asarray(eloss).mean()),
            rtol=0.1)
    finally:
        bf.win_free()
        bf.shutdown()
