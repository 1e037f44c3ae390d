"""Set-up: process start to the end of the warm-up job (host clock)."""


def read(run):
    return run.setup_s
