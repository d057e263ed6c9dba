"""XLA backend compiles inside the window per update applied (JAX's
compile event; a program loaded from the persistent cache does not
count)."""


def read(run):
    return run.compiles_in_window / len(run.window.samples)
