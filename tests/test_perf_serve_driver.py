"""`perf/drivers/serve.py`'s `run()` against a stand-in engine and tracer
on a clock made by hand: the window comes first, the traced slice follows
its close, the profiler's stop (which stalls, while the engine serves on)
follows the last reading of the queue; an empty queue at the window's close
or at the slice's end is exit code 1, one that empties during the stall is
a result.  No JAX, no chip."""
import json
import os
import sys

import numpy as np
import pytest

# a copy of perf/tests/test_serve_driver.py, which tier-1 does not collect:
# the program's faster scheduler leans on this order (window, traced slice,
# stall), so the repo's own tests guard it.  `perf` lies at the repo's
# root, which perf/tests/conftest.py puts on the path for its own tests.
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import run  # noqa: E402

serve = run.load_file("drivers", "serve")

WINDOW, SLICE, STALL = 50.0, 3.0, 30.0


class Clock:
    def __init__(self):
        self.t = 1000.0

    def now(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Handle:
    """A finished request of two tokens, admitted and served at `t`."""
    finished, status = True, "done"

    def __init__(self, t):
        self.t_tokens, self.t_done, self.tokens = [t, t + 0.5], t + 0.5, [1, 2]
        self.trace = self
        self._t = t

    def snapshot(self):
        return [{"name": "admitted", "t": self._t - 0.25}]


class Engine:
    """A queue of `count` that falls by `rate` requests a second of the
    hand-made clock, whoever advances it."""

    def __init__(self, clock, events, count, rate):
        self.clock, self.events = clock, events
        self.count, self.rate, self.t0 = count, rate, clock.t
        self.closed = False

    def stats(self):
        used = int(self.rate * (self.clock.t - self.t0))
        self.events.append("stats")
        return {"queue_depth": max(0, self.count - used),
                "admitted": min(self.count, used), "steps": 10 * used,
                "prefill_chunk": {"jobs": 0, "chunk": 32},
                "blocks_free": 8, "blocks_total": 32, "shed": {},
                "evicted": {}}

    def close(self):
        self.closed = True


class Tracer:
    def __init__(self, clock, events):
        self.clock, self.events = clock, events

    def start(self):
        self.events.append("start")

    def mark(self):
        self.events.append("mark")

    def stop(self):
        self.events.append("stop")
        self.clock.sleep(STALL)


@pytest.fixture
def made(monkeypatch):
    clock, events, lines = Clock(), [], []
    monkeypatch.setattr(serve, "now", clock.now)
    monkeypatch.setattr(serve.time, "sleep", clock.sleep)
    monkeypatch.setattr(serve.ledger, "read_ring",
                        lambda since: events.append("ring") or "no ring")

    def build(count, rate):
        drv = serve.Driver(
            cell={"trace_seconds": SLICE,
                  "engine": {"block_size": 16, "max_batch": 4}},
            config={}, traffic={"name": "made-mix", "count": count},
            seed=1, reference=None, generate=None, say=lines.append)
        drv.engine = Engine(clock, events, count, rate)
        drv.chunk = 32
        drv.handles = [({"prompt": np.zeros(8, np.int32), "max_new": 2},
                        Handle(clock.t + 1.0 + k)) for k in range(5)]
        return drv, Tracer(clock, events)

    return build, clock, events, lines


def test_the_window_comes_first_and_the_stop_after_the_last_reading(made):
    build, clock, events, lines = made
    drv, tracer = build(count=1000, rate=4.0)
    t0 = clock.t
    record = drv.run(WINDOW, tracer)
    # open and close of the window, then the slice, its reading, the ring's
    # copy, and only then the stall; what follows is the wait for `due`
    assert events[:7] == ["stats", "stats", "start", "mark", "stats", "ring",
                          "stop"]
    assert (record["t_open"], record["t_close"]) == (t0, t0 + WINDOW)
    assert (record["trace_t0"], record["trace_t1"]) == (
        t0 + WINDOW, t0 + WINDOW + SLICE)
    assert record["ring"] == "no ring"
    assert record["window_s"] == WINDOW
    line = json.loads(lines[-1])
    assert line["queue_depth_open_close"] == [1000, 800]
    assert line["queue_depth_last"] == 788           # 53 s at 4 a second
    assert line["backlog_left_share"] == record["backlog_left_share"] == 0.788
    assert record["attempted"] == 5 and record["failed"] == 0
    assert not drv.engine.closed


def test_an_untraced_run_reads_the_queue_at_the_close_only(made):
    build, clock, events, lines = made
    drv, _ = build(count=1000, rate=4.0)
    record = drv.run(WINDOW, None)
    assert events[:2] == ["stats", "stats"] and "ring" not in events
    assert "trace_t0" not in record and "ring" not in record
    assert record["backlog_left_share"] == 0.8


@pytest.mark.parametrize("rate, where, consumed, events_then", [
    # 1000 requests at 25 a second last 40 s: gone inside the window (50 s)
    (25.0, "inside the window", "20.0", ["stats", "stats"]),
    # at 19 a second they last 52.6 s: gone inside the slice (53 s)
    (19.0, "inside the traced slice", "18.9",
     ["stats", "stats", "start", "mark", "stats", "ring", "stop"]),
])
def test_a_queue_that_empties_where_it_is_measured_is_exit_code_1(
        made, rate, where, consumed, events_then):
    build, clock, events, lines = made
    drv, tracer = build(count=1000, rate=rate)
    engine = drv.engine
    with pytest.raises(SystemExit) as e:
        drv.run(WINDOW, tracer)
    said = str(e.value.code)          # a string: exit code 1, said on stderr
    assert where in said and "'made-mix'" in said and "count 1000" in said
    assert "queue 1000 at the window's open" in said
    assert f"{consumed} requests consumed a second" in said
    assert events == events_then      # a tracer that started was stopped
    assert engine.closed and drv.engine is None


def test_a_queue_that_empties_during_the_stall_is_a_result(made):
    build, clock, events, lines = made
    # 1000 requests at 18 a second last 55.6 s: 46 left at the slice's end,
    # gone 2.6 s into the profiler's stall
    drv, tracer = build(count=1000, rate=18.0)
    record = drv.run(WINDOW, tracer)
    assert drv.engine.stats()["queue_depth"] == 0
    assert json.loads(lines[-1])["queue_depth_last"] == 46
    assert record["backlog_left_share"] == 0.046
    assert record["end_to_end"]["serve_tokens_per_s"] > 0
