"""The scheduler's ring of iteration records as a measurement (ISSUE 28).

One tiny CPU engine serves a few requests, one of them on a prefix-cache
hit and some under a `jax.profiler` trace, and is closed; everything
below reads what it left behind: `telemetry.profiler.iterations()` (the
records outlive the engine, are contiguous, sum to their wall, carry the
lanes' pool use and a stamp per prefill chunk) and the trace's host plane
(the scheduler's phases as `serving.<cause>` spans whose `it` is the
record's step).
"""
import glob
import time

import jax
import numpy as onp
import pytest

from incubator_mxnet_tpu.telemetry import profiler

V, C, DFF, L, H, MAXLEN = 61, 16, 32, 1, 2, 64
BS, CHUNK = 8, 4
PROMPTS = [onp.arange(3, 20, dtype=onp.int32),          # 17 tokens
           onp.arange(3, 20, dtype=onp.int32),          # the same: a hit
           onp.array([5, 9, 2, 44, 17, 8], onp.int32),
           onp.arange(30, 41, dtype=onp.int32)]


def _engine(**kw):
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
    return ServingEngine(net, max_batch=2, block_size=BS, max_queue=8,
                         prefill_chunk=CHUNK, poll_interval=0.001, **kw)


def _admitted(req) -> dict:
    return next(e for e in req.trace.snapshot() if e["name"] == "admitted")


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """What a closed engine left: its name, the requests, the span of
    time it served in, and the trace taken over its second half."""
    eng = _engine()
    t_start = time.monotonic()
    first = eng.submit(PROMPTS[0], 5)
    first.result(timeout=120)               # its blocks are published now
    reqs = [first] + [eng.submit(p, 6, seed=i)
                      for i, p in enumerate(PROMPTS[1:])]
    for r in reqs:
        r.result(timeout=120)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t_trace = time.monotonic()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        traced = [eng.submit(p, 5, seed=10 + i)
                  for i, p in enumerate(PROMPTS)]
        for r in traced:
            r.result(timeout=120)
        t_traced = time.monotonic()
    finally:
        jax.profiler.stop_trace()
    name = eng._name
    eng.close()
    return {"engine": name, "requests": reqs + traced, "t_start": t_start,
            "t_trace": t_trace, "t_traced": t_traced,
            "xplane": glob.glob(trace_dir
                                + "/plugins/profile/*/*.xplane.pb")[0]}


def test_records_outlive_the_engine_contiguous_and_whole(run):
    assert run["engine"] not in profiler.profilers()     # closed
    recs, held = profiler.iterations(run["t_start"], engine=run["engine"])
    assert held and len(recs) >= 10
    assert [r.step for r in recs] == list(range(recs[0].step,
                                                recs[0].step + len(recs)))
    for before, rec in zip(recs, recs[1:]):
        assert rec.t0 == before.t1
    for rec, after in zip(recs, recs[1:] + [None]):
        assert len(rec.causes) == len(profiler.CAUSES)
        assert sum(rec.causes) == pytest.approx(rec.t1 - rec.t0,
                                                rel=0.05, abs=1e-6)
        # a step's fetch and its locked commit were both timed, apart;
        # and, where the step after was handed over before this one was
        # read (the loop runs one step ahead), that call
        by_cause = rec.as_dict()["causes"]
        assert min(by_cause[c] for c in ("device_step", "commit")) > 0
        if after is not None and after.ahead:
            assert by_cause["dispatch"] > 0
    assert sum(r.ahead for r in recs) > len(recs) // 2
    # the window cuts by the commit's stamp
    mid = recs[len(recs) // 2].t1
    early, _ = profiler.iterations(run["t_start"], mid,
                                   engine=run["engine"])
    late, _ = profiler.iterations(mid, engine=run["engine"])
    assert [r.step for r in early + late] == [r.step for r in recs]
    assert all(r.t1 < mid for r in early) and late[0].t1 == mid


def test_chunk_stamps_count_every_prefilled_token_once(run):
    recs, _ = profiler.iterations(run["t_start"], engine=run["engine"])
    stamps = {}
    for rec in recs:
        for rid, start, n, t in rec.chunks:
            assert rec.t0 <= t <= rec.t1
            stamps.setdefault(rid, []).append((start, n, t))
    cached = {r.rid: _admitted(r)["cached_tokens"] for r in run["requests"]}
    assert sorted(cached.values())[-1] == 16 and min(cached.values()) == 0
    assert sum(n for ss in stamps.values() for _, n, _ in ss) == sum(
        len(r.prompt) - cached[r.rid] for r in run["requests"])
    for r in run["requests"]:
        ss = stamps[r.rid]
        # a hit's cached tokens are in no chunk; the rest, in order
        assert [s for s, _, _ in ss] == list(
            range(cached[r.rid], len(r.prompt), CHUNK))
        assert ss[0][0] + sum(n for _, n, _ in ss) == len(r.prompt)
        ts = [t for _, _, t in ss]
        assert ts == sorted(ts)
        assert _admitted(r)["t"] <= ts[0] and ts[-1] <= r.t_first


def test_positions_written_fit_the_lanes_reservations(run):
    recs, _ = profiler.iterations(run["t_start"], engine=run["engine"])
    assert any(r.positions_written for r in recs)
    for rec in recs:
        assert rec.blocks_total == 2 * (MAXLEN // BS)   # max_batch lanes
        assert rec.block_size == BS
        assert 0 <= rec.positions_written <= rec.blocks_reserved * BS
        assert rec.occupancy <= 2 and rec.queue_depth >= 0


def test_scheduler_phases_are_spans_on_the_profilers_clock(run):
    """Under a `jax.profiler` trace nobody in the program started, the
    scheduler thread's line of the host plane holds the phases, and
    `it` joins each to the ring's record of that iteration."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(run["xplane"])
    lines = [[(e.name, dict(e.stats).get("it"), e.start_ns, e.duration_ns)
              for e in line.events if e.name.startswith("serving.")]
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    lines = [ln for ln in lines if ln]
    assert len(lines) == 1                  # one thread: the scheduler's
    spans = lines[0]
    by_it = {}
    for name, it, _s, _d in spans:
        by_it.setdefault(it, set()).add(name)
    recs, _ = profiler.iterations(run["t_trace"], run["t_traced"],
                                  engine=run["engine"])
    whole = [r for r in recs if r.t0 >= run["t_trace"]]
    assert len(whole) >= 5
    handed = 0
    for rec, after in zip(whole, whole[1:] + [None]):
        assert {"serving.lock_wait", "serving.bookkeeping",
                "serving.device_step", "serving.commit"} <= by_it[rec.step]
        # the iteration that read this record's step had handed the next
        # one over first, unless no lane was left to run
        if after is not None and after.ahead:
            handed += 1
            assert {"serving.gather_params", "serving.dispatch"} \
                <= by_it[rec.step]
        if rec.chunks:
            assert {"serving.prefill_chunk", "serving.dispatch"} \
                <= by_it[rec.step]
    assert handed >= 3
    assert {it for it in by_it} <= {r.step for r in recs} | {
        recs[0].step - 1, recs[-1].step + 1}
    # spans nest as the phases do: a dispatch lies inside the step or
    # chunk that made it, and a commit never inside a lock_wait
    outer = [(s, s + d) for n, _, s, d in spans
             if n in ("serving.device_step", "serving.prefill_chunk")]
    for n, _, s, d in spans:
        if n == "serving.dispatch":
            assert any(a <= s and s + d <= b for a, b in outer)
    waits = [(s, s + d) for n, _, s, d in spans if n == "serving.lock_wait"]
    for n, _, s, d in spans:
        if n == "serving.commit":
            assert not any(a <= s and s + d <= b for a, b in waits)


def test_switched_off_ledger_leaves_the_ring_alone(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVING_PROFILER", "0")
    eng = _engine()
    try:
        assert not eng.profiler.enabled
        eng.submit(PROMPTS[2], 4).result(timeout=120)
        assert eng.stats()["steps"] > 0
    finally:
        eng.close()
    assert profiler.iterations(engine=eng._name)[0] == []
    assert eng.profiler.recent_steps() == []
