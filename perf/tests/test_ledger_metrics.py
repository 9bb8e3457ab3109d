"""The four readers of the scheduler's own ledger (`perf/metrics/
sched_host_ms.py`, `sched_host_p99_ms.py`, `prefill_tokens_per_s.py`,
`kv_pool_written_pct.py`) on a ring made by hand, and in a whole traced
rehearsal beside the metrics the cell had before."""
import importlib.util
import os

import pytest

from incubator_mxnet_tpu.telemetry import profiler
from perf.tests import rehearse

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE = "gpt2-medium.serve-batch"
NEW = ["sched_host_ms", "sched_host_p99_ms", "prefill_tokens_per_s",
       "kv_pool_written_pct"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "..", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def iteration(k, **causes):
    """Iteration k: [10 + k, 11 + k), the given causes in ms, the rest of
    its second a wait for the device; a chunk stamped at each quarter."""
    vals = [causes.get(c, 0.0) * 1e-3 for c in profiler.CAUSES]
    vals[profiler.CAUSES.index("device_step")] = 1.0 - sum(vals)
    t0 = 10.0 + k
    return profiler.Iteration(
        "made", k + 1, t0, t0 + 1.0, tuple(vals), occupancy=2,
        queue_depth=5, blocks_reserved=6 + k, blocks_total=16, block_size=8,
        positions_written=32 + 8 * k,
        chunks=((7, 0, 32, t0 + 0.25), (7, 32, 20, t0 + 0.75)))


@pytest.fixture
def ring(monkeypatch):
    made = profiler.StepRing(8)
    monkeypatch.setattr(profiler, "_steps", made)
    return made


def record(t_open, t_close, **work):
    return {"t_open": t_open, "t_close": t_close,
            "window_s": t_close - t_open,
            "work": dict({"prompt_tokens": 0, "chunks": 0}, **work)}


def test_a_window_that_cuts_two_records_at_its_edges(ring, capsys):
    for k in range(5):      # commits at 11, 12, 13, 14, 15
        ring.push(iteration(k, commit=1.0 + k, dispatch=2.0, gc=0.5,
                            wait=100.0))
    # [11.5, 14.5) cuts iteration 1 and iteration 4: a record belongs to
    # the window its commit lies in, so 1, 2 and 3 count, whole
    rec = record(11.5, 14.5)
    assert reader("sched_host_ms")(rec) == pytest.approx(
        (2.0 + 3.0 + 4.0) / 3 + 2.0 + 0.5)    # the idle poll is no host time
    out = capsys.readouterr().out
    assert '"iterations": 3' in out and '"dispatch": 2.0' in out
    assert reader("sched_host_p99_ms")(rec) == pytest.approx(
        6.5 - 0.02, abs=1e-9)                 # numpy's linear percentile
    out = capsys.readouterr().out
    assert '"samples": 3' in out and '"most_of_the_excess": "commit"' in out
    # written: (40, 48, 56) of 128 positions; reserved: (7, 8, 9) of 16
    assert reader("kv_pool_written_pct")(rec) == pytest.approx(37.5)
    assert '"reserved_pct_mean": 50.0' in capsys.readouterr().out


def test_a_chunk_stamp_on_each_side_of_the_windows_edges(ring, capsys):
    for k in range(5):
        ring.push(iteration(k))
    # stamps at k + 10.25 (32 tokens) and k + 10.75 (20 tokens); the
    # window [11.5, 14.5) holds 11.75, 12.25, 12.75, 13.25, 13.75 and the
    # 14.25 of the iteration still under way at its close
    rec = record(11.5, 14.5, prompt_tokens=150, chunks=6)
    assert reader("prefill_tokens_per_s")(rec) == pytest.approx(
        (20 + 32 + 20 + 32 + 20 + 32) / 3.0)
    out = capsys.readouterr().out
    assert '"chunks_stamped": 6' in out
    assert '"stamped_minus_interpolated": 6' in out


@pytest.mark.parametrize("name", NEW)
def test_a_ring_that_lost_the_opening_says_nothing(ring, capsys, name):
    for k in range(12):     # capacity 8: iterations 0 to 3 are dropped
        ring.push(iteration(k))
    assert reader(name)(record(12.5, 20.5)) is None
    assert "no longer holds" in capsys.readouterr().out
    assert reader(name)(record(14.0, 20.5)) is not None    # 4 began at 14


@pytest.mark.parametrize("name", NEW)
def test_a_copy_taken_in_time_outlasts_the_ring(ring, capsys, name):
    from perf.work import ledger

    for k in range(6):      # commits at 11 to 16; the ring holds all six
        ring.push(iteration(k))
    rec = record(11.5, 14.5, prompt_tokens=150, chunks=6)
    before = reader(name)(rec)
    kept = dict(rec, ring=ledger.read_ring(rec["t_open"]))
    assert [r.step for r in kept["ring"][0]] == [2, 3, 4, 5, 6]
    for k in range(6, 10):  # the engine serves on: iterations 0, 1 drop
        ring.push(iteration(k))
    assert reader(name)(rec) is None
    assert "no longer holds" in capsys.readouterr().out
    assert reader(name)(kept) == before is not None
    # a program without a ring: the copy is the reason, said by the reader
    assert reader(name)(dict(rec, ring="no ring here")) is None
    assert "no ring here" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_an_empty_ring_or_none_at_all_says_nothing(ring, capsys,
                                                   monkeypatch, name):
    assert reader(name)(record(11.5, 14.5)) is None        # ledger off
    assert "MXTPU_SERVING_PROFILER" in capsys.readouterr().out
    monkeypatch.delattr(profiler, "iterations")            # an older program
    assert reader(name)(record(11.5, 14.5)) is None
    assert "no ring" in capsys.readouterr().out


def test_a_traced_rehearsal_reports_them_beside_the_old_metrics():
    result = rehearse.run_tiny(SERVE, seed=2**31 + 28, seconds=1.5, trace=1)
    assert result["correct"]
    got = result["metrics"]
    # the readers of the device's trace find no TPU plane on the CPU
    assert set(got) >= set(NEW) | {"batch_occupancy_pct", "step_mfu.serve",
                                   "device_idle_pct.serve",
                                   "output_tokens_per_s"}
    assert 0 < got["sched_host_ms"]["value"] <= got["sched_host_p99_ms"][
        "value"]
    assert 0 < got["kv_pool_written_pct"]["value"] <= 100
    assert got["prefill_tokens_per_s"]["value"] > 0
    # the scheduler's phases reach the reduced trace's idle gaps by name
    assert any(name.startswith("serving.") for name, _s in
               result["breakdown"]["idle_gaps"])
