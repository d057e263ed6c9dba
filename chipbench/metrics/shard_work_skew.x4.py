"""The largest over the cell's chips, over their mean, of the seconds
of the traced window in which an op other than a collective ran: the
balance of padded device work per chip, 1.0 where every chip is as
busy. The ops run over blocks of one capacity on every chip, so this
does not show how evenly the hash spreads live rows
(``chipbench/collectives.py``)."""
from chipbench import collectives as X


def read(run):
    return X.work_skew(run.trace, run.chips)
