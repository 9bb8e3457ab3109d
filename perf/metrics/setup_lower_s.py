"""Seconds of a start inside JAX's trace and lowering: the union, over all
threads, of the program's `compile.trace` and `compile.lower` spans within
set-up, less what lies inside a `compile.backend` span.  Every start pays
it whatever the compile cache holds: a Pallas kernel is lowered to Mosaic
here.  Source: the program's set-up record (`perf/work/setup.py`, whose
line `setup_phases` stands before the result)."""
from perf.work import setup


def read(record):
    got = setup.phases(record, "setup_lower_s")
    return None if got is None else got["seconds"]["lower"]
