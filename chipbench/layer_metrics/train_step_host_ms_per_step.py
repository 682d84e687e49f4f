"""Layer `train step`: host milliseconds of one `step_fn` call of
training.make_decentralized_train_step (its `train_step` span: the cache
lookup on the state's structure, argument flattening, pjit dispatch), inside
chipbench's `dispatch` span."""

from chipbench import program_spans


def read(run):
    return program_spans.train_step_host_ms_per_step(program_spans.recorded())
