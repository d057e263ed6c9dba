"""Rows applied per second: every row of every update in the window,
over the whole window."""


def read(run):
    return sum(rows for _, _, rows in run.window.samples) / run.window.length
