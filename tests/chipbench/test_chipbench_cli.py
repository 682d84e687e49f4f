"""`python -m chipbench` as the driver starts it, at the `rehearsal` sizes on
CPU devices: one child per job kind, and the refusals that print no result.
A rehearsal walks the control flow; it is never a measurement."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import manifest  # noqa: E402


def _chipbench(*args, devices=1, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable, "-m", "chipbench", *args], cwd=REPO,
                          env=env, capture_output=True, text=True, timeout=timeout)


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _result(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


def test_pushsum_rehearsal_prints_the_contracts_object_and_nothing_else():
    proc = _chipbench("--workload", "bert-base-pushsum-1chip", "--seed",
                      str(2**31 + 77), "--seconds", "1", "--trace", "0", "--rehearse")
    result, earlier = _result(proc)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 10
    assert set(result["metrics"]) == {"train_samples_s_chip", "step_ms_p95", "setup_s"}
    for m in manifest.load_manifest()["end_to_end"]:
        got = result["metrics"][m["name"]]
        assert set(got) == {"value", "unit"} and got["unit"] == m["unit"]
        assert isinstance(got["value"], float) and got["value"] > 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                                "memory_peak_bytes": 0}
    # every number compared is printed beside its limit, on an earlier line
    checks = [l for l in earlier if l.startswith("chipbench: check ")]
    for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap", "change1_rel_l2",
                 "assoc_p_gap"):
        assert any(name in l and "(limit " in l and " ok" in l for l in checks), name
    assert any("step-time samples" in l for l in earlier)
    assert any("compiles in window 0" in l for l in earlier)


def test_four_rank_traced_rehearsal_names_no_share_of_a_peak_and_no_idle_share():
    proc = _chipbench("--workload", "resnet50-atc-exp2-4chip", "--seed", "4",
                      "--seconds", "1", "--trace", "1", "--rehearse", devices=4)
    result, earlier = _result(proc)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["device"]["count"] == 4
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"] and "window_s" not in result["device"]
    cell = manifest.resolve("resnet50-atc-exp2-4chip")
    assert set(result["metrics"]) <= {m["name"] for m in cell.per_layer}
    # the host's own spans are read anywhere; what only a chip's trace or a
    # chip's peak can give is left out of a rehearsal's line
    assert {"host_dispatch_ms_per_step", "host_dispatch_ms_p95"} <= set(result["metrics"])
    for name in ("mfu_pct", "idle_pct", "peak_hbm_gb", "atc_over_allreduce",
                 "compute_ms_per_step", "collective_ms_per_step"):
        assert name not in result["metrics"], name
    assert any('"collective_permute_in_lowered_step": true' in l for l in earlier)
    assert any("leaves on 4 of 4 device(s)" in l for l in earlier)


def test_every_number_compared_closes_stderr_and_the_result_line():
    """The contract's record of a run that is not correct keeps the end of
    stderr and the end of the last line: both end with each number compared
    beside its limit."""
    proc = _chipbench("--workload", "bert-base-atc-b128-1chip", "--seed",
                      str(2**31 + 28), "--seconds", "1", "--trace", "0", "--rehearse")
    result, earlier = _result(proc)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device",
                            "checks"]  # `checks` comes last
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_samples_s_chip", "step_ms_p95", "setup_s"}
    checks = result["checks"]
    limits = manifest.resolve("bert-base-atc-b128-1chip").module("reference").LIMITS
    assert {name: checks[name]["limit"] for name in limits} == limits
    assert all(checks[name]["value"] <= limits[name] for name in limits)
    for name in ("structure_mismatch", "chips_without_leaves", "compiles_in_window",
                 "failed_steps", "nonfinite_checked_losses"):
        assert checks[name] == {"value": 0, "limit": 0}
    last = proc.stderr.strip().splitlines()[-len(checks):]
    assert [l.split()[2].rstrip(":") for l in last] == list(checks)
    assert all(l.startswith("chipbench: check ") and "(limit " in l for l in last)
    # the jitted step on one rank: no neighbour, so no permute in the step
    assert any('"collective_permute_in_lowered_step": false' in l for l in earlier)
    # the mix's batch is laid over the configuration's, the rehearsal's over both
    cell = manifest.resolve("bert-base-atc-b128-1chip")
    assert cell.sizes()["per_rank_batch"] == 128
    assert cell.sizes(rehearse=True)["per_rank_batch"] == 4
    assert cell.mix["optimizer"]["name"] == "adamw"


def test_the_decoder_cell_rehearses_correct_under_its_warm_up():
    """The banded kernels in interpret mode, the dropless expert layer, the
    chunked loss, AdamW at 1.5e-7, 3e-7 and 4.5e-7 in the three checked steps:
    every number inside the limits that the constant rate was held to."""
    name = "smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip"
    proc = _chipbench("--workload", name, "--seed", str(2**31 + 34), "--seconds",
                      "1", "--trace", "0", "--rehearse", timeout=300)
    result, earlier = _result(proc)
    assert list(result)[-1] == "checks" and result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 3
    limits = manifest.resolve(name).module("reference").LIMITS
    checks = result["checks"]
    assert {n: checks[n]["limit"] for n in limits} == limits
    assert all(0 <= checks[n]["value"] <= limits[n] for n in limits)
    # the parameters did move: a first update of nothing would compare zeros
    assert checks["change1_rel_l2"]["value"] > 0
    assert any('"collective_permute_in_lowered_step": false' in l for l in earlier)


@pytest.mark.parametrize("args,message", [
    (["--workload", "resnet50-atc-1chip", "--seed", "1", "--seconds", "1",
      "--trace", "0"], "found no TPU"),
    (["--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
      "--trace", "0", "--rehearse"], "no workload named"),
])
def test_refusals_exit_2_and_print_no_result(args, message):
    proc = _chipbench(*args)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert message in proc.stderr
