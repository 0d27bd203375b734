"""Programs compiled (or loaded from the cache) between the window's
first and last instant. Must be 0: the run is not `correct` otherwise."""


def read(run):
    return run.facts["compiles_in_window"]
