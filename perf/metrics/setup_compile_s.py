"""Seconds of a start inside XLA's compile or the persistent compile
cache's load: the union, over all threads, of the program's
`compile.backend` spans within set-up (JAX's event wraps the cache's lookup
and, on a miss, the compile).  Source: the program's set-up record
(`perf/work/setup.py`)."""
from perf.work import setup


def read(record):
    got = setup.phases(record, "setup_compile_s")
    return None if got is None else got["seconds"]["compile"]
