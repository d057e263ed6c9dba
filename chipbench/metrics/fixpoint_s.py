"""Seconds per complete fixpoint: the window's length over the
fixpoints completed in it (LDBC Graphalytics' processing time)."""


def read(run):
    return run.window.length / len(run.window.samples)
