"""Set-up seconds: process start to the window (imports, pool, compile, warm-up)."""


def read(run):
    return run.setup_s
