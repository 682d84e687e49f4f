"""`python -m chipbench.routing`, the tool the warm-up cell's `why` rests on
(its routing stays what the seed made it), at sizes a CPU holds: `held_rows`
of the decoder's plain reference against a count taken layer by layer from
the reference's own router and layers, and the tool's walk through a window."""

import json
import os
import sys

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import manifest, routing, seeded  # noqa: E402

CELL = "smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip"


def test_held_rows_are_the_assignments_the_references_router_sends_here():
    cell = manifest.resolve(CELL)
    ref = cell.module("reference")
    # the rehearsal's top-4 of 4 experts sends every token everywhere: top-2
    # of 4 with 2 held, so that the count depends on the router
    sizes = dict(cell.sizes(rehearse=True), moe_num_active_primary_experts=2)
    params, _ = seeded.make_weights(ref, sizes, seed=2**31 + 3)
    # the seeded router at hidden 64 barely tells tokens apart: widen it
    params = {p: a * (40.0 if p[-1] == "router" else 1.0) for p, a in params.items()}
    (x, _), = seeded.make_batches(ref, sizes, 2**31 + 3, ranks=1, pool=1)
    ids = x[0]
    got = np.asarray(jax.jit(lambda p, i: ref.held_rows(p, i, sizes))(params, ids))
    # by hand: each sequence through the reference's own layers, the router's
    # logits read off the stream as `layer` reads them, the two largest picked
    windows, held = ref.layer_windows(sizes), sizes["moe_num_primary_experts_held"]
    want = np.zeros(len(windows), int)
    for seq in np.asarray(ids):
        stream = params[("embed", "embedding")][seq]
        for i, window in enumerate(windows):
            logits = np.asarray(stream, np.float64) @ np.asarray(
                params[(f"layer_{i}", "router")], np.float64)
            chosen = np.argsort(-logits, axis=1)[:, :2]
            want[i] += int(np.sum(chosen < held))
            stream = ref.layer(stream, params, f"layer_{i}", window, sizes, False)
    assert got.tolist() == want.tolist()
    tokens = ids.shape[0] * ids.shape[1]
    assert got.shape == (sizes["num_hidden_layers"],)
    assert all(0 < n < 2 * tokens for n in got) and len(set(got.tolist())) > 1
    # every expert held: every assignment lands here, in every layer
    whole = dict(sizes, moe_num_primary_experts_held=sizes["moe_num_primary_experts"])
    params_whole, _ = seeded.make_weights(ref, whole, seed=5)
    assert np.asarray(ref.held_rows(params_whole, ids, whole)).tolist() \
        == [2 * tokens] * sizes["num_hidden_layers"]


def test_the_tool_counts_before_and_after_a_window_at_rehearsal_sizes(capsys):
    assert routing.main(["--workload", CELL, "--seeds", "2", "--seconds", "0.5",
                         "--rehearse"]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    assert [r["seed"] for r in rows] == [300, 300 + 178956971]
    sizes = manifest.resolve(CELL).sizes(rehearse=True)
    # top-4 of 4 experts, 2 held: every token reaches both, before and after
    even = sizes["per_rank_batch"] * sizes["seq_len"] * 4 * 2 / 4
    for r in rows:
        assert r["failed"] == 0 and r["steps_in_window"] >= 2
        assert r["even_rows"] == even
        assert r["held_rows_first_step"] == r["held_rows_last_step"] \
            == [int(even)] * sizes["num_hidden_layers"]
        assert 0 < r["step_ms_median_first_ten"] and 0 < r["step_ms_median_last_ten"]
        assert r["step_ms_median"] > 0


def test_the_tool_refuses_to_count_where_there_is_no_chip(capsys):
    assert routing.main(["--workload", CELL, "--seeds", "1"]) == 2
    captured = capsys.readouterr()
    assert captured.out == "" and "no TPU" in captured.err
