"""Layer `eager ops, windows`: child spans per round, i.e. the compiled-program
calls the library makes at the sites it names (a site that launches two
programs, `reset`, is one span), to set beside the trace's
`launches_per_round`."""

from chipbench import program_spans


def read(run):
    return program_spans.window_programs_per_round(program_spans.recorded())
