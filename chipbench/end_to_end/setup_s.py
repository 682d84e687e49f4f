"""Process start to the first timed step: imports, bf.init, seeded weights and
batches, compile or cache fetch of this cell's programs, the three checked
steps and the warm-up."""


def read(run):
    return run["setup_s"]
