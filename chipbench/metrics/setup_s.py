"""Seconds from process start to the window's start: imports, weights,
engine, the mix's prefixes prefilled, every program compiled or loaded."""


def read(run):
    return run.setup_s
