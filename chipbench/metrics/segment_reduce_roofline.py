"""Share, in percent, of the HBM roofline that the segment-reduce kernel
(``kernels/segment_reduce.py``) reached in the window.

Its least bytes are the values and segment ids read once and the
segments written once; the tiled path's scalar-prefetched block bounds
and its re-reads of row blocks across segment tiles are left out.

Its ops are ``segment_reduce_pallas.N``, after its jitted wrapper, as
the probe's are ``merge_probe_pallas.N`` in a TPU v5 lite trace."""
from chipbench import trace as T

KERNEL = "segment_reduce_pallas"


def min_bytes(results: list[int], operands: list[int]) -> int:
    return sum(results) + sum(operands[-2:])


def read(run):
    return T.roofline_share(run.trace, KERNEL, min_bytes, run.device_kind)
