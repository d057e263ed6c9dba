"""Seconds from the start of the process to the start of the window:
data generation, loading or compiling the programs, and the warm-up."""


def read(run):
    return run.setup_s
