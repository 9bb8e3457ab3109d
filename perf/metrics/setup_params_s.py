"""Seconds of a start in gluon's parameters: the self time of the
program's `setup.initialize`, `setup.deferred_init`, `setup.cast` and
`setup.set_data` spans within set-up, the compile spans inside them taken
out.  Source: the program's set-up record (`perf/work/setup.py`)."""
from perf.work import setup


def read(record):
    got = setup.phases(record, "setup_params_s")
    return None if got is None else got["seconds"]["params"]
