"""Unified timeline profiler + per-step stall attribution (ISSUE 17).

Unit tests pin the ledger's attribution math with injected clocks
(causes sum to step wall exactly, GC carve never double-counts,
hiccup threshold over the rolling p50, bounded rings), the GC-hook
pause accounting against a real ``gc.collect()``, the merged
chrome-trace's conformance + lane structure + ts monotonicity (via the
same `validate_chrome_trace` the CI smoke uses), and the disabled-path
overhead budget (<5 µs per note, the PR 8 idiom).  One module-scope
engine integration covers `/stallz`, `/profilez?seconds=`,
`capture_profile()`, the `/varz` config section, and the live
sum-to-wall invariant.
"""
import gc
import json
import threading
import time
import urllib.request

import numpy as onp
import pytest

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.telemetry import profiler
from incubator_mxnet_tpu.telemetry.profiler import (EngineProfiler,
                                                    Iteration, StepRing,
                                                    validate_chrome_trace)

_POLL = 0.001


@pytest.fixture
def telemetry_on():
    telemetry.enable()
    yield
    telemetry.disable()


class FakeClock:
    """Deterministic perf_counter stand-in: advance() by hand."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _prof(clock, gc_box=None, **kw):
    gc_box = gc_box if gc_box is not None else [0.0]
    kw.setdefault("enabled", True)
    kw.setdefault("steps", StepRing())      # not the process's ring
    p = EngineProfiler("test", clock=clock,
                       gc_seconds=lambda: gc_box[0], **kw)
    return p, gc_box


# ---------------------------------------------------------------------- #
# attribution math (injected clocks — no engine, no jax)
# ---------------------------------------------------------------------- #
def test_ledger_sums_to_wall_exactly():
    clk = FakeClock()
    p, _ = _prof(clk)
    clk.advance(0.010)
    p.note("device_step", 0.010)
    clk.advance(0.002)
    p.note("bookkeeping", 0.002)
    clk.advance(0.003)                      # unattributed host time
    p.end_step(rids=(1, 2), occupancy=2, queue_depth=0, step=1)
    [rec] = p.recent_steps()
    assert rec["wall_s"] == pytest.approx(0.015)
    assert rec["causes"]["device_step"] == pytest.approx(0.010)
    assert rec["causes"]["bookkeeping"] == pytest.approx(0.002)
    assert rec["causes"]["host_other"] == pytest.approx(0.003)
    assert sum(rec["causes"].values()) == pytest.approx(rec["wall_s"])
    assert p.invariant_violations == 0
    # every cause key the ledger can emit is in the documented set
    assert set(rec["causes"]) <= set(profiler.CAUSES)


def test_step_window_spans_from_previous_commit():
    """Prefill interleave and idle waits BETWEEN decode steps belong to
    the next step's ledger — the wall is commit-to-commit, so causes
    still sum to it."""
    clk = FakeClock()
    p, _ = _prof(clk)
    clk.advance(0.004)
    p.note("prefill_chunk", 0.004)          # interleaved prefill
    clk.advance(0.001)
    p.note("wait", 0.001)                   # idle poll
    clk.advance(0.010)
    p.note("device_step", 0.010)
    p.end_step(step=1)
    [rec] = p.recent_steps()
    assert rec["wall_s"] == pytest.approx(0.015)
    assert rec["causes"]["prefill_chunk"] == pytest.approx(0.004)
    assert rec["causes"]["wait"] == pytest.approx(0.001)
    assert sum(rec["causes"].values()) == pytest.approx(rec["wall_s"])


def test_gc_carve_comes_out_of_residue_only():
    clk = FakeClock()
    p, gc_box = _prof(clk)
    # 10ms wall: 6ms attributed to device, 4ms residue; 2ms of GC fell
    # in the residue -> gc=2ms, host_other=2ms, sum still exact
    clk.advance(0.010)
    p.note("device_step", 0.006)
    gc_box[0] += 0.002
    p.end_step(step=1)
    [rec] = p.recent_steps()
    assert rec["causes"]["gc"] == pytest.approx(0.002)
    assert rec["causes"]["host_other"] == pytest.approx(0.002)
    assert sum(rec["causes"].values()) == pytest.approx(0.010)
    # GC pause larger than the residue (it interrupted a timed phase,
    # already inside that phase's interval): carve clamps to residue
    clk.advance(0.010)
    p.note("device_step", 0.009)
    gc_box[0] += 0.005
    p.end_step(step=2)
    rec = p.recent_steps()[-1]
    assert rec["causes"]["gc"] == pytest.approx(0.001)
    assert rec["causes"]["host_other"] == 0.0
    assert sum(rec["causes"].values()) == pytest.approx(0.010)
    assert p.invariant_violations == 0


def test_hiccup_threshold_and_record_detail():
    clk = FakeClock()
    p, _ = _prof(clk, hiccup_k=3.0)
    # build a rolling baseline of 10ms steps — no hiccups while the
    # window is warming up or while steps stay near p50
    for i in range(10):
        clk.advance(0.010)
        p.note("device_step", 0.010)
        assert p.end_step(step=i + 1) is None
    # one 50ms step (5x the 10ms p50, > k=3): flagged, injected cause
    # dominates, full detail recorded
    clk.advance(0.050)
    p.note("device_step", 0.050)
    hic = p.end_step(rids=(7, 9), occupancy=2, queue_depth=3, step=11)
    assert hic is not None
    assert hic["dominant"] == "device_step"
    assert hic["wall_s"] == pytest.approx(0.050)
    assert hic["p50_s"] == pytest.approx(0.010)
    assert hic["ratio"] == pytest.approx(5.0)
    assert hic["rids"] == [7, 9]
    assert hic["occupancy"] == 2 and hic["queue_depth"] == 3
    assert p.hiccups_total == 1
    assert p.recent_stalls() == [hic]
    sz = p.stallz()
    assert sz["hiccups"][0]["step"] == 11
    assert sz["invariant_violations"] == 0


def test_no_hiccup_before_min_samples():
    clk = FakeClock()
    p, _ = _prof(clk, hiccup_k=3.0)
    # first steps wildly varied — never flagged: no baseline yet
    for i, w in enumerate([0.001, 0.050, 0.002, 0.060]):
        clk.advance(w)
        p.note("device_step", w)
        assert p.end_step(step=i + 1) is None
    assert p.hiccups_total == 0


def test_hiccup_ring_is_bounded():
    clk = FakeClock()
    p, _ = _prof(clk, hiccup_k=2.0, ring=4)
    for i in range(8):
        clk.advance(0.010)
        p.note("device_step", 0.010)
        p.end_step(step=i + 1)
    for i in range(10):                     # 10 hiccups into a ring of 4
        clk.advance(0.100)
        p.note("device_step", 0.100)
        p.end_step(step=100 + i)
    assert p.hiccups_total >= 4
    stalls = p.recent_stalls()
    assert len(stalls) <= 4
    assert p.stallz()["ring_cap"] == 4


def test_stall_table_shares():
    clk = FakeClock()
    p, _ = _prof(clk)
    for i in range(4):
        clk.advance(0.010)
        p.note("device_step", 0.008)
        p.note("bookkeeping", 0.002)
        p.end_step(step=i + 1)
    rows = {r["cause"]: r for r in p.stall_table()}
    assert rows["device_step"]["share"] == pytest.approx(0.8, abs=0.01)
    assert rows["bookkeeping"]["share"] == pytest.approx(0.2, abs=0.01)
    assert rows["device_step"]["per_step_ms"] == pytest.approx(8.0, abs=0.1)
    # sorted by total, biggest first
    assert p.stall_table()[0]["cause"] == "device_step"


def test_set_enabled_reanchors_window():
    clk = FakeClock()
    p, _ = _prof(clk, enabled=False)
    p.note("device_step", 1.0)              # dropped: disabled
    assert p.end_step(step=1) is None and p.steps == 0
    clk.advance(5.0)                        # a long disabled era
    p.set_enabled(True)
    clk.advance(0.010)
    p.note("device_step", 0.010)
    p.end_step(step=2)
    [rec] = p.recent_steps()
    # the disabled era is NOT attributed to the first enabled step
    assert rec["wall_s"] == pytest.approx(0.010)


def test_phase_charges_self_time_and_sums_to_wall():
    """`with prof.phase(cause)`: a phase is charged its own time only —
    `dispatch` inside `device_step`, `wait` inside `bookkeeping` — so
    the causes still sum to the wall."""
    clk = FakeClock()
    p, _ = _prof(clk)
    with p.phase("bookkeeping"):
        clk.advance(0.002)
        with p.phase("wait"):
            clk.advance(0.005)
        clk.advance(0.001)
    with p.phase("device_step"):
        clk.advance(0.001)                  # fault hook
        with p.phase("dispatch"):
            clk.advance(0.0015)
        clk.advance(0.030)                  # the blocking fetch
    clk.advance(0.0005)                     # unattributed
    p.end_step(step=1)
    [rec] = p.recent_steps()
    assert rec["causes"]["bookkeeping"] == pytest.approx(0.003)
    assert rec["causes"]["wait"] == pytest.approx(0.005)
    assert rec["causes"]["dispatch"] == pytest.approx(0.0015)
    assert rec["causes"]["device_step"] == pytest.approx(0.031)
    assert rec["causes"]["host_other"] == pytest.approx(0.0005)
    assert sum(rec["causes"].values()) == pytest.approx(rec["wall_s"])
    assert p.invariant_violations == 0
    with pytest.raises(KeyError):           # the legacy cause is gone
        p.note("prefill", 0.001)


def test_lock_wait_ends_where_the_locked_phase_begins():
    clk = FakeClock()
    p, _ = _prof(clk)

    class Lock:                             # acquiring takes 4 ms
        def __enter__(self):
            clk.advance(0.004)

        def __exit__(self, *exc):
            clk.advance(0.0001)             # releasing: nobody's phase

    with p.phase("lock_wait"), Lock(), p.phase("commit"):
        clk.advance(0.002)
    p.end_step(step=1)
    [rec] = p.recent_steps()
    assert rec["causes"]["lock_wait"] == pytest.approx(0.004)
    assert rec["causes"]["commit"] == pytest.approx(0.002)
    assert rec["causes"]["host_other"] == pytest.approx(0.0001)


def test_step_ring_drops_the_oldest_and_says_so():
    clk = FakeClock()
    ring = StepRing(8)
    p, _ = _prof(clk, steps=ring)
    starts = []
    for i in range(12):
        starts.append(clk.t)
        clk.advance(0.010)
        p.note("device_step", 0.010)
        p.chunk(rid=i, start=0, n=4, t=clk.t)
        p.end_step(step=i + 1, occupancy=1, blocks_reserved=3,
                   blocks_total=16, block_size=8,
                   positions_written=20)
    recs, held = ring.window(starts[4])
    assert held and [r.step for r in recs] == list(range(5, 13))
    assert recs[0].t0 == starts[4] and recs[0].chunks == ((4, 0, 4,
                                                           recs[0].t1),)
    recs, held = ring.window(starts[2])     # began in a dropped record
    assert not held and [r.step for r in recs] == list(range(5, 13))
    recs, held = ring.window(starts[6], starts[9])
    assert held and [r.step for r in recs] == [6, 7, 8]     # t1 in [since, until)
    assert ring.window(engine="other")[0] == []
    assert StepRing(8).window(0.0) == ([], False)       # empty: not held
    assert isinstance(recs[0], Iteration)
    assert recs[0].as_dict()["positions_written"] == 20
    # recent_steps and the merged trace's lane read the same ring
    assert [r["step"] for r in p.recent_steps(3)] == [10, 11, 12]
    lane = p.chrome_events(since=starts[10])
    assert [(n, round(d, 6)) for n, _c, _t, d in lane] == [
        ("device_step", 0.01)] * 3


# ---------------------------------------------------------------------- #
# GC hook pause accounting (real gc.callbacks)
# ---------------------------------------------------------------------- #
def test_gc_hooks_account_collect_pauses():
    profiler.install_gc_hooks()
    profiler.install_gc_hooks()             # idempotent
    try:
        assert profiler.gc_hooks_installed()
        before = profiler.gc_pause_seconds()
        cut0 = time.perf_counter()
        gc.collect()
        gc.collect()
        after = profiler.gc_pause_seconds()
        assert after > before               # pauses accumulated, this tid
        # window-filtered, NOT len() deltas: the event deque is bounded
        # (maxlen) and may already be full after a long test session
        evs = profiler.gc_events(since=cut0)
        assert len(evs) >= 2
        ev = evs[-1]
        assert ev["tid"] == threading.get_ident()
        assert ev["dur"] >= 0 and ev["gen"] in (-1, 0, 1, 2)
        # since= filters by event end time
        cut = time.perf_counter()
        gc.collect()
        recent = profiler.gc_events(since=cut)
        assert recent and all(e["t0"] + e["dur"] >= cut for e in recent)
    finally:
        profiler.uninstall_gc_hooks()
        profiler.uninstall_gc_hooks()       # idempotent
    assert not profiler.gc_hooks_installed()


# ---------------------------------------------------------------------- #
# chrome-trace validator + merged capture (no engine)
# ---------------------------------------------------------------------- #
def test_validator_accepts_minimal_trace():
    assert validate_chrome_trace({"traceEvents": []}) == []
    tr = {"traceEvents": [
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 2,
         "args": {"name": "lane"}},
        {"name": "a", "ph": "X", "pid": 1, "tid": 2, "ts": 10.0,
         "dur": 5.0},
        {"name": "b", "ph": "i", "pid": 1, "tid": 2, "ts": 20.0},
    ]}
    assert validate_chrome_trace(tr) == []
    assert validate_chrome_trace(json.dumps(tr)) == []


def test_validator_rejects_malformed_traces():
    assert validate_chrome_trace("not json{")[0].startswith("not JSON")
    assert validate_chrome_trace({"events": []}) \
        == ["top level is not {'traceEvents': [...]}"]
    bad_dur = {"traceEvents": [{"name": "a", "ph": "X", "pid": 1,
                                "tid": 1, "ts": 1.0, "dur": -3.0}]}
    assert any("bad dur" in p for p in validate_chrome_trace(bad_dur))
    backwards = {"traceEvents": [
        {"name": "a", "ph": "i", "pid": 1, "tid": 1, "ts": 20.0},
        {"name": "b", "ph": "i", "pid": 1, "tid": 1, "ts": 10.0}]}
    assert any("backwards" in p for p in validate_chrome_trace(backwards))
    missing = {"traceEvents": [{"ph": "i", "ts": 1.0, "pid": 1}]}
    assert any("missing" in p for p in validate_chrome_trace(missing))
    unknown = {"traceEvents": [{"name": "a", "ph": "Z", "pid": 1,
                                "tid": 1, "ts": 1.0}]}
    assert any("unknown ph" in p for p in validate_chrome_trace(unknown))


def test_merged_trace_lanes_and_order(telemetry_on):
    clk = FakeClock(time.perf_counter())
    p = EngineProfiler("laneeng", clock=time.perf_counter, enabled=True)
    profiler.register(p)
    try:
        p.note("device_step", 0.005)        # lands in the event deque
        p.end_step(step=1)
        with telemetry.span("unit_span"):
            time.sleep(0.001)
        tr = profiler.merged_chrome_trace()
        assert validate_chrome_trace(tr) == []
        evs = tr["traceEvents"]
        # scheduler lane present and NAMED via thread_name metadata
        sched = [e for e in evs if e.get("cat") == "scheduler"]
        assert sched and all(e["args"]["engine"] == "laneeng"
                             for e in sched)
        names = {e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "laneeng scheduler" in names
        # tracer span present on its real thread's lane
        tele = [e for e in evs if e.get("cat") == "telemetry"]
        assert any(e["name"] == "unit_span" for e in tele)
        # non-metadata events are globally ts-sorted
        ts = [e["ts"] for e in evs if e["ph"] != "M"]
        assert ts == sorted(ts)
        # metadata events carry no ts and come first
        assert all("ts" not in e for e in evs if e["ph"] == "M")
    finally:
        profiler.unregister("laneeng")
    assert "laneeng" not in profiler.profilers()


def test_capture_window_filters_old_events(telemetry_on):
    p = EngineProfiler("wineng", clock=time.perf_counter, enabled=True)
    profiler.register(p)
    try:
        p.note("device_step", 0.005)
        p.end_step(step=1)
        time.sleep(0.01)
        cut = time.perf_counter()
        tr = profiler.merged_chrome_trace(since=cut)
        old = [e for e in tr["traceEvents"]
               if e.get("cat") == "scheduler"]
        assert old == []                    # pre-cut events filtered
        p.note("device_step", 0.005)
        p.end_step(step=2)
        tr = profiler.merged_chrome_trace(since=cut)
        fresh = [e for e in tr["traceEvents"]
                 if e.get("cat") == "scheduler"]
        assert fresh
    finally:
        profiler.unregister("wineng")


def test_capture_seconds_bounded():
    t0 = time.perf_counter()
    tr = profiler.capture(0.05)
    assert time.perf_counter() - t0 < profiler.MAX_CAPTURE_S
    assert validate_chrome_trace(tr) == []


# ---------------------------------------------------------------------- #
# disabled path rides the near-zero budget (PR 8 idiom)
# ---------------------------------------------------------------------- #
def test_profiler_disabled_overhead_budget():
    p = EngineProfiler("off", enabled=False)
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        p.note("device_step", 0.001)
        p.end_step(step=1)
    per_call = (time.perf_counter() - t0) / (2 * n)
    # generous CI bound: each disabled call is one flag read,
    # microseconds would already mean a broken fast path
    assert per_call < 5e-6, f"disabled path costs {per_call * 1e9:.0f} ns/call"
    assert p.steps == 0 and p.recent_steps() == []


def test_profiler_enabled_overhead_budget():
    """One decode iteration's ledger — seven phases with their spans and
    the record — costs 15 µs on a quiet core of this sandbox, where the
    same loop with the ledger off reads 2.5 µs; a slower or busier
    machine gets the budget scaled by what its disabled loop reads."""
    lock = threading.Lock()

    def one_round(p, n=100):
        t0 = time.perf_counter()
        for i in range(n):
            with p.phase("lock_wait"), lock, p.phase("bookkeeping"):
                pass
            with p.phase("gather_params"):
                pass
            with p.phase("device_step"):
                with p.phase("dispatch"):
                    pass
            with p.phase("lock_wait"), lock, p.phase("commit"):
                pass
            p.end_step(rids=(1, 2), occupancy=2, queue_depth=3,
                       step=i + 1, blocks_reserved=10, blocks_total=16,
                       positions_written=70)
        return (time.perf_counter() - t0) / n

    telemetry.disable()
    ring = StepRing()
    p_off = EngineProfiler("off", enabled=False)
    p_on = EngineProfiler("on", enabled=True, steps=ring)
    # the two loops take turns, so that a busy spell of the host (six
    # test workers share it) falls on both, in rounds short enough (a
    # millisecond) to fit between two preemptions; each loop is judged
    # by its own quietest round
    off = on = float("inf")
    for _ in range(70):
        off = min(off, one_round(p_off))
        on = min(on, one_round(p_on))
    budget = 15e-6 * max(1.0, off / 2.5e-6)
    assert on < budget, (f"an iteration's ledger costs {on * 1e6:.1f} us "
                         f"(disabled loop {off * 1e6:.1f} us)")
    assert len(ring.window()[0]) == 7000


def test_enabled_note_stays_cheap_when_telemetry_off():
    # ledger on, telemetry collection off: notes accumulate into a dict
    # but no trace events or histograms record
    telemetry.disable()
    p = EngineProfiler("cheap", enabled=True)
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        p.note("device_step", 0.001)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"note costs {per_call * 1e9:.0f} ns/call"
    assert p.chrome_events() == []          # no events without telemetry


# ---------------------------------------------------------------------- #
# engine integration: live ledger + endpoints (one module-scope engine)
# ---------------------------------------------------------------------- #
V, C, DFF, L, H, MAXLEN = 61, 16, 32, 1, 2, 64
PROMPT = onp.array([3, 7, 11, 2, 9], onp.int32)


@pytest.fixture(scope="module")
def engine():
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.serving import ServingEngine

    mx.random.seed(0)
    net = TransformerLM(vocab=V, units=C, hidden_size=DFF, num_layers=L,
                        num_heads=H, max_len=MAXLEN, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 4), jnp.int32)))
    telemetry.enable()
    eng = ServingEngine(net, max_batch=2, block_size=8, max_queue=4,
                        poll_interval=_POLL, http_port=0)
    rs = [eng.submit(PROMPT, 6, seed=i) for i in range(4)]
    for r in rs:
        r.result(timeout=120)
    assert eng.drain(timeout=30)
    yield eng
    eng.close()
    telemetry.disable()


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=10) as r:
        return r.status, r.read().decode()


def test_engine_ledger_invariant_holds_live(engine):
    prof = engine.profiler
    assert prof.steps > 0
    assert prof.invariant_violations == 0
    for rec in prof.recent_steps():
        total = sum(rec["causes"].values())
        assert total == pytest.approx(rec["wall_s"],
                                      rel=0.05, abs=1e-6)
    rows = {r["cause"] for r in engine.stall_table()}
    assert "device_step" in rows and "prefill_chunk" in rows


def test_engine_capture_profile_has_lanes(engine):
    tr = engine.capture_profile(0)          # 0 = everything buffered
    assert validate_chrome_trace(tr) == []
    cats = {e.get("cat") for e in tr["traceEvents"]
            if e.get("ph") != "M"}
    assert "request" in cats                # requestlog lifecycle lane
    assert "scheduler" in cats              # engine phase lane
    assert "program" in cats                # perf note_timing lane


def test_engine_http_stallz_profilez_varz(engine):
    base = f"http://127.0.0.1:{engine.http_port}"
    code, body = _get(base, "/stallz")
    assert code == 200
    sz = json.loads(body)["engines"][engine._name]
    assert sz["steps"] > 0 and sz["invariant_violations"] == 0
    code, body = _get(base, "/profilez?seconds=0.05")
    assert code == 200
    assert validate_chrome_trace(body) == []
    code, body = _get(base, "/varz")
    cfg = json.loads(body)["config"][engine._name]
    assert cfg["max_batch"] == 2 and cfg["block_size"] == 8
    assert cfg["kv_dtype"] == "model"
    assert cfg["attn_impl"] in ("pallas", "dense")
    assert cfg["prefill_chunk"] == engine._chunk
    assert cfg["prefix_cache"] is True
    assert cfg["slo"]["objective"] == pytest.approx(0.99)
    assert cfg["profiler"]["enabled"] in (True, False)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(base, "/profilez?seconds=bogus")
    assert ei.value.code == 400


def test_engine_flight_section_carries_stalls(engine):
    sec = engine._flight_section()
    assert "stalls" in sec


def test_engine_injected_stall_flagged_as_hiccup(engine):
    prof = engine.profiler
    before = prof.hiccups_total
    # warm the rolling window, then inject one slow device step via the
    # fault-hook seam; it must be flagged with device_step dominating.
    # The stall is a multiple of the p50 the ledger has OBSERVED (the hook
    # runs on the scheduler's thread, the ledger's one writer), so the
    # verdict does not hang on how loaded the host is; and the record is
    # found by its step, not by being the newest
    fired = {"n": 0, "step": None, "slept": 0.0}

    def hook(phase):
        if phase == "step":
            fired["n"] += 1
            if fired["n"] == 12:
                fired["step"] = engine._stats["steps"] + 1
                fired["slept"] = max(
                    0.25, 4 * prof.hiccup_k * (prof._p50 or 0.0))
                time.sleep(fired["slept"])

    engine.set_fault_hook(hook)
    try:
        rs = [engine.submit(PROMPT, 10, seed=100 + i) for i in range(4)]
        for r in rs:
            r.result(timeout=120)
    finally:
        engine.set_fault_hook(None)
    assert fired["step"] is not None and prof.hiccups_total > before
    [hic] = [h for h in prof.recent_stalls() if h["step"] == fired["step"]]
    assert hic["causes"]["device_step"] >= fired["slept"]
    assert hic["dominant"] == "device_step"
    assert sum(hic["causes"].values()) == pytest.approx(
        hic["wall_s"], rel=0.05, abs=1e-6)
