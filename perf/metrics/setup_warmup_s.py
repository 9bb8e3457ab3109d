"""Seconds of a start in the warm-up wave: from the start of the program's
first `setup.first_call.<kind>` span (a serving program's first call, in
the scheduler's thread) to the window's open, less the compile and
lowering inside that stretch.  Source: the program's set-up record
(`perf/work/setup.py`)."""
from perf.work import setup


def read(record):
    got = setup.phases(record, "setup_warmup_s")
    return None if got is None else got["seconds"]["warmup"]
