"""Device seconds per fixpoint spent in XLA sort operations on the
first chip (the engine's arrangements, dedupes and merges sort int64
keys). A TPU v5 lite trace names them ``sort.N``."""
from chipbench import trace as T


def read(run):
    return T.seconds_per_step(run.trace, run.window, T.is_sort)
