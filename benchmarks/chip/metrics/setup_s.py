"""Host-clock seconds from the process's start to the first timed step:
imports, weights, batches, compiling (or loading) the step, and the
checked steps."""


def read(run: dict):
    return run["setup_s"]
