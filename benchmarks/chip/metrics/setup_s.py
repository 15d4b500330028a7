"""setup_s: process start to the first timed request (graph generation,
build to version 0, warm-up), on the host clock."""


def read(run):
    return run.setup_s
