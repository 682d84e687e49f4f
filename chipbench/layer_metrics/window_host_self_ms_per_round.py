"""Layer `eager ops, windows`: the window ops' self time per round: their spans
minus what their child spans (one per compiled-program call) cover.  What is
left is the library's own Python, its weight matrices and transfers, and the
eager jnp calls that launch programs the library never named."""

from chipbench import program_spans


def read(run):
    return program_spans.window_host_self_ms_per_round(program_spans.recorded())
