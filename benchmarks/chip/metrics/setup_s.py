"""Set-up seconds: process start to the first timed request (imports, TPU
start, plan, schedule, compile or cache load, payload, warm-up)."""


def read(run):
    return run.setup.get("setup_s")
