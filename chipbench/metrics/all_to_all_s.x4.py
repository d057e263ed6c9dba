"""Device seconds per traced fixpoint, on the first chip, in the
all-to-all ops of the sharded engine's repartitions
(``engine/shard.py`` ``repartition_rows``): on a v5e, the ops
``%all_to_all.N = s32[4,...] all-to-all(...)``, three an iteration of
the four-chip Reach step (``chipbench/collectives.py``)."""
from chipbench import collectives as X
from chipbench import trace as T


def read(run):
    return T.seconds_per_step(run.trace, run.window, X.is_all_to_all)
