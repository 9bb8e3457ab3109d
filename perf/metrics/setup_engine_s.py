"""Seconds of a start in the serving engine's construction: the self time
of the program's `setup.engine` and `setup.programs` spans within set-up
(pools, gathered or stacked weights, host packing), the compile spans
inside them taken out.  Source: the program's set-up record
(`perf/work/setup.py`)."""
from perf.work import setup


def read(record):
    got = setup.phases(record, "setup_engine_s")
    return None if got is None else got["seconds"]["engine"]
