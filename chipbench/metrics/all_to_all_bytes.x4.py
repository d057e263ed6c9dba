"""Bytes the all-to-all ops of the sharded engine's repartitions
delivered on the first chip per traced fixpoint, from the result shapes
in their HLO text: the padded ``[S, cap, arity]`` buffers, which move
whole whatever their live rows (``chipbench/collectives.py``)."""
from chipbench import collectives as X


def read(run):
    return X.all_to_all_bytes(run.trace, run.window, run.chips)
