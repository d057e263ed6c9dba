"""Share of the traced window, in percent, in which no operation ran on
the device, averaged over the chips the cell uses."""
from chipbench import trace as T


def read(run):
    return T.idle_share(run.trace, run.chips)
