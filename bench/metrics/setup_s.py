"""setup_s: seconds from process start to the first timed job:
JAX and chip start-up, building the simulated system and its inputs,
compiling or loading programs from the cache, and the untimed warm-up
job."""


def read(run):
    return run.setup_s
