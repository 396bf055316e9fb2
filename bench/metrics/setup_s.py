"""Process start to window start (host clock): imports, corpus, index build or
cache read, upload, system build, compile-cache reads and warm-up."""

UNIT = "s"


def read(run):
    return run.setup_s
