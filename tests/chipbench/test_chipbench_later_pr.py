"""What a later `model_config` PR brings, as files and entries only: a cut
configuration that states its cut, a language-model program whose `apply_fn`
returns the chunked scalar loss and hands over its own `loss_fn`, a mix with
sizes of its own, and per-layer metrics appended after the last entry.  A toy
of each is added to a temporary checkout and driven through the job kind, the
runner and the command, and no file that was there is edited.  The step of a
program that gives no loss keeps its lowered text."""

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import bluefog_tpu as bf

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import manifest, optimizers, runner  # noqa: E402

CONFIG = {
    "name": "toy-lm", "source": "a paper",
    "sizes": {"vocab_size": 64, "hidden_size": 16, "num_hidden_layers": 2,
              "seq_len": 8, "per_rank_batch": 4},
    # the published number beside the number held
    "published": {"vocab_size": 512, "num_hidden_layers": 48},
    "reduced": ["vocab_size", "num_hidden_layers"],
    "deployment": "one chip of eight that share a layer: an eighth of the "
                  "vocabulary; 2 of 48 layers, the others on further stages",
    "precision": {"compute": "float32", "params": "float32"},
    "optimizer": {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9},
    "guarantee": "every step mixes with exactly the topology's weights",
    "assumed": ["seeded weights: normal, std 0.5"],
    "rehearsal": {"seq_len": 4},
}

REFERENCE = '''
import jax
import jax.numpy as jnp

LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "delta_norm_gap": 1e-3,
          "assoc_p_gap": 0.0}


def param_shapes(sizes):
    d = sizes["hidden_size"]
    p = {("embed",): (sizes["vocab_size"], d)}
    for i in range(sizes["num_hidden_layers"]):
        p[(f"layer_{i}", "w")] = (d, d)
    return p, {}


def init_rule(path, shape):
    return ("normal", 0.5)


def input_shapes(sizes):
    ids = ((sizes["seq_len"],), "int32", sizes["vocab_size"])
    return {"x": ids, "y": ids}


def loss_fn(p, s, ids, y, sizes, lower=False):
    x = p[("embed",)][ids]
    for i in range(sizes["num_hidden_layers"]):
        x = x + jnp.tanh(x @ p[(f"layer_{i}", "w")])
    logits = x @ p[("embed",)].T  # [B, S, V] at once: the plain way
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked), {}
'''

PROGRAM = '''
import jax
import jax.numpy as jnp


def build(sizes):
    def apply_fn(variables, ids, labels=None):
        """The scalar loss, the head taken in two chunks of the sequence."""
        p = variables["params"]
        x = p["embed"][ids]
        for i in range(sizes["num_hidden_layers"]):
            x = x + jnp.tanh(x @ p[f"layer_{i}"]["w"])
        total = 0.0
        for h, y in zip(jnp.split(x, 2, axis=1), jnp.split(labels, 2, axis=1)):
            logits = h @ p["embed"].T
            picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
            total = total + jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)
        return total / labels.size

    return {"apply_fn": apply_fn, "has_batch_stats": False,
            "loss_fn": lambda loss, labels: loss}
'''

FLOPS = '''
def train_flops_per_sample(sizes):
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    return 6 * sizes["seq_len"] * (sizes["num_hidden_layers"] * d * d + d * v)
'''

MIX = {"job": "spmd_train_step", "communication_type": "neighbor_allreduce",
       "mode": "atc", "topology": {"graph": "ExponentialTwoGraph", "kwargs": {}},
       "mixing": "exp2", "pool": 4, "sizes": {"per_rank_batch": 6}}

METRIC = '''
def read(run):
    return run["window"]["step_ms_median"]
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The benchmark's directory and manifest copied, the toy's files added
    and its entries appended: (root, the files that were there)."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "chipbench"
    shutil.copytree(os.path.join(REPO, "chipbench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "toy-lm.json").write_text(json.dumps(CONFIG))
    (bench / "reference" / "toy-lm.py").write_text(REFERENCE)
    (bench / "program" / "toy-lm.py").write_text(PROGRAM)
    (bench / "flops" / "toy-lm.py").write_text(FLOPS)
    (bench / "traffic" / "toy-lm-mix.json").write_text(json.dumps(MIX))
    (bench / "layer_metrics" / "toy_step_ms.py").write_text(METRIC)
    new = manifest.load_manifest()
    new["configs"].append({
        "name": "toy-lm", "source": "a paper", "reduced": CONFIG["reduced"],
        "file": "chipbench/configs/toy-lm.json", "why": "test"})
    new["workloads"].append({"name": "toy-lm-cell", "config": "toy-lm",
                             "traffic": "toy-lm-mix", "chips": 1, "why": "test"})
    new["per_layer"].append({  # after the last entry
        "name": "toy_step_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "train step",
        "moves": "step_ms_p95", "workloads": ["toy-lm-cell"]})
    for metric in new["per_layer"]:
        if metric["name"] == "train_step_host_ms_per_step":
            metric["workloads"].append("toy-lm-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    return root, before


def test_the_toys_cut_sizes_and_metrics_resolve(checkout):
    root, before = checkout
    cell = manifest.resolve("toy-lm-cell", root=str(root))
    assert cell.config["reduced"] == ["vocab_size", "num_hidden_layers"]
    assert cell.config["deployment"] and cell.config["published"]["vocab_size"] == 512
    assert cell.sizes()["per_rank_batch"] == 6 and cell.sizes()["seq_len"] == 8
    assert cell.sizes(rehearse=True)["seq_len"] == 4
    names = [m["name"] for m in cell.per_layer]
    assert names[-1] == "toy_step_ms" and "train_step_host_ms_per_step" in names
    assert "window_host_ms_per_round" not in names
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_program_with_its_own_loss_reaches_the_step_and_is_correct(checkout, capsys):
    """`apply_fn` returns a scalar: under the library's default loss the step
    could not be traced.  The plain reference takes [B, S, V] logits at once,
    and the run is held to its limits as any cell's."""
    root, _ = checkout
    cell = manifest.resolve("toy-lm-cell", root=str(root))
    program = cell.module("program").build(cell.sizes(rehearse=True))
    assert program["loss_fn"]("the loss", None) == "the loss"
    args = argparse.Namespace(workload=cell.name, seed=2**31 + 28, seconds=0.3,
                              trace=0, rehearse=True)
    result = runner.run(args, time.perf_counter(), cell)
    out = capsys.readouterr().out
    assert result["correct"] is True and result["attempted"] > 3
    assert result["checks"]["loss_gap"]["value"] <= 1e-4
    assert "check loss_gap" in out and "NOT OK" not in out
    # without the program's loss the same job cannot build its step
    with pytest.raises(ValueError, match="out of bounds"):
        _step_without_the_programs_loss(cell)


def _step_without_the_programs_loss(cell):
    ses = runner.Session(cell, rehearse=True)
    try:
        ses.load(1)
        ses.program = {k: v for k, v in ses.program.items() if k != "loss_fn"}
        ses.make_job().step(0)
    finally:
        bf.shutdown()


def test_the_command_runs_the_toy_from_its_checkout(checkout):
    """As the driver starts it: from the root of a checkout that is not this
    repository's, the benchmark's files found beside BENCHMARK.json."""
    root, before = checkout
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    env.pop("BLUEFOG_TIMELINE", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", "toy-lm-cell", "--seed",
         "28", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=str(root), env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["toy_step_ms"]["value"] > 0
    assert result["metrics"]["train_step_host_ms_per_step"]["value"] > 0
    assert "cache None" in proc.stdout  # a rehearsal keeps no compile cache
    changed = [str(p) for p, b in before.items() if p.read_bytes() != b]
    assert changed == []


def test_the_step_of_a_program_without_a_loss_keeps_its_lowered_text():
    """`resnet50` at the rehearsal sizes: the job's step lowers to the text of
    the call as it stood before a program could give a loss."""
    from bluefog_tpu.optim import CommunicationType
    from bluefog_tpu.training import make_decentralized_train_step

    from chipbench import seeded

    def sha(step_fn, state, batch):
        text = jax.jit(step_fn).lower(*state, *batch).as_text()
        return hashlib.sha256(text.encode()).hexdigest()

    ses = runner.Session(manifest.resolve("resnet50-atc-1chip"), rehearse=True)
    try:
        ses.load(3)
        assert "loss_fn" not in ses.program
        job = ses.make_job()
        init_fn, step_fn = make_decentralized_train_step(
            ses.program["apply_fn"], optimizers.make(ses.opt_spec), ses.ctx.mesh,
            communication_type=CommunicationType.neighbor_allreduce,
            plan=ses.ctx.plan, mode="atc", has_batch_stats=True)
        params, stats = (seeded.nest(t) for t in ses.build_weights(ses.key))
        state = (params, stats, init_fn(params))
        assert sha(job.step_fn, job.state, ses.batches[0]) \
            == sha(step_fn, state, ses.batches[0])
    finally:
        bf.shutdown()
