"""Job kind `spmd_train_step`: the jitted SPMD step a user gets from
`training.make_decentralized_train_step` with its defaults (donate=True,
steps_per_call=1), ATC or allreduce as the mix says.  The loss is the
library's default (softmax cross-entropy of the logits `apply_fn` returns)
unless `program/<config>.py` gives a `loss_fn` of its own: a language model
whose `apply_fn` returns the chunked scalar loss hands over the identity.
Copied from chip_smoke._ResNetJob (sound: ran on the chip in PR 23)."""

import jax

from bluefog_tpu.optim import CommunicationType
from bluefog_tpu.training import make_decentralized_train_step

from chipbench import optimizers, seeded

# what the trace shows of this job (one XLA program per step)
STEP_ANCHOR = r"^jit_local_step"
WINDOW_PROGRAMS = ()
# the library's own spans that a step of this job records
PROGRAM_SPANS = ("train_step",)


class Job:
    def __init__(self, spec):
        self.spec = spec
        ctx, mix = spec.ctx, spec.mix
        self.comm = CommunicationType[mix["communication_type"]]
        gossips = self.comm == CommunicationType.neighbor_allreduce
        program = spec.program
        own_loss = {"loss_fn": program["loss_fn"]} if "loss_fn" in program else {}
        init_fn, self.step_fn = make_decentralized_train_step(
            program["apply_fn"], optimizers.make(spec.opt_spec), ctx.mesh,
            communication_type=self.comm, plan=ctx.plan if gossips else None,
            mode=mix.get("mode", "atc"),
            has_batch_stats=program["has_batch_stats"], **own_loss)
        params = seeded.nest(spec.params)
        stats = seeded.nest(spec.stats)
        self.state = (params, stats, init_fn(params))
        self.expect_permutes = gossips and ctx.size > 1

    def placement(self):
        return self.state

    def step(self, k):
        with self.spec.spans.span("input"):
            x, y = self.spec.batches[k % len(self.spec.batches)]
        *state, loss, _acc = self.step_fn(*self.state, x, y)
        self.state = tuple(state)
        return loss, loss  # (what marks the step done, its loss per rank)

    def params(self):
        return seeded.flatten(self.state[0])

    def first_gradient(self):
        return seeded.flatten(
            optimizers.first_gradient(self.spec.opt_spec, self.state[2]))

    def assoc_p(self):
        return None  # W is doubly stochastic: no weight rides along

    def structure(self):
        """Gossip is collective-permutes, and only where there are
        neighbours (chip_smoke.phase_resnet's rule)."""
        x, y = self.spec.batches[0]
        text = jax.jit(self.step_fn).lower(*self.state, x, y).as_text()
        found = "collective_permute" in text
        return {"collective_permute_in_lowered_step": found,
                "expected": self.expect_permutes,
                "ok": found == self.expect_permutes}

    def close(self):
        self.state = None
