"""Seconds of the first call of each program the window runs: XLA compile,
or load from the persistent cache, and one execution, in set-up."""


def read(run):
    return run.setup.get("compile_s")
