"""Share, in percent, of the HBM roofline that the merge-path probe
kernel (``kernels/merge_probe.py``) reached in the window.

Its least bytes are the probe and build key words read once and the two
rank outputs written once; the two scalar-prefetched block-bound
vectors and the re-reads of build blocks across probe blocks are left
out, so that another algorithm for the same probe is judged on the
same work.

In a TPU v5 lite trace the kernel's ops are ``merge_probe_pallas.N``,
after its jitted wrapper (``pallas_call`` sets no ``name=``), each
event named by its HLO text, which gives the shapes
(``chipbench/tests/data/``)."""
from chipbench import trace as T

KERNEL = "merge_probe_pallas"


def min_bytes(results: list[int], operands: list[int]) -> int:
    return sum(results) + sum(operands[2:])


def read(run):
    return T.roofline_share(run.trace, KERNEL, min_bytes, run.device_kind)
