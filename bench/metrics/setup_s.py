"""Seconds from process start to the first timed chunk: imports, the
host-side system build, ``driver.init()``, compilation or cache load,
and one warm-up chunk."""


def read(run):
    return run.setup_s
