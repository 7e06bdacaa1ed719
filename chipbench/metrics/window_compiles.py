"""Programs JAX traced inside the window (compiled, or loaded from the
persistent cache): warm-up should leave none."""


def read(run):
    return float(run.window_compiles)
