"""build_s: ``SPCService.from_config`` to version 0, ended by reading the
built index back (host clock)."""


def read(run):
    return run.build_s
