"""The set-up record (`telemetry.profiler.setup_phase`, `setup_spans`): a
process's start by phase, from inside the program.

One tiny engine's start, recorded into a record of the test's own, pins
the spans of every site (gluon's initialise, deferred shapes, cast and
hand-over; the engine, its programs, each program family's first call in
the scheduler's thread) and their nesting, the self times against the
wall, and that with telemetry off each serving program is lowered once a
start.  A fresh `jit` pins the listener on JAX's compile events; the rest
pins the record's bound, threads that record at once, the kill switch and
the phase's span in a `jax.profiler` trace.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.telemetry import profiler

PROMPT = onp.array([3, 7, 11, 2, 9], onp.int32)


def union_measure(intervals):
    total, end = 0.0, None
    for lo, hi in sorted(intervals):
        if end is None or lo > end:
            total += hi - lo
            end = hi
        elif hi > end:
            total += hi - end
            end = hi
    return total


def self_time(span, spans):
    kids = [(max(s["t0"], span["t0"]), min(s["t1"], span["t1"]))
            for s in spans if s["parent"] == span["id"]]
    return span["t1"] - span["t0"] - union_measure(kids)


@pytest.fixture
def record(monkeypatch):
    made = profiler.SetupRecord()
    monkeypatch.setattr(profiler, "_setup", made)
    monkeypatch.delenv("MXTPU_SERVING_PROFILER", raising=False)
    return made


@pytest.fixture(scope="module")
def start():
    """One start: a net built, its shapes resolved, cast, weights handed
    in, an engine built and two requests served; the spans it recorded."""
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.serving import ServingEngine

    with pytest.MonkeyPatch.context() as mp:
        made = profiler.SetupRecord()
        mp.setattr(profiler, "_setup", made)
        mp.delenv("MXTPU_SERVING_PROFILER", raising=False)
        telemetry.disable()
        # widths no other test uses: the programs are this start's own
        net = TransformerLM(vocab=71, units=16, hidden_size=40, num_layers=2,
                            num_heads=2, max_len=64, dropout=0.0)
        net.initialize()
        head = nn.Dense(3)                  # its input width comes later
        head.initialize()
        head(NDArray(jnp.ones((2, 5), jnp.float32)))
        net.cast("bfloat16")
        for p in net.collect_params().values():
            p.set_data(onp.asarray(p.data()._data, onp.float32))
        eng = ServingEngine(net, max_batch=2, block_size=8, max_queue=4,
                            prefill_chunk=8)
        try:
            for h in [eng.submit(PROMPT, 4), eng.submit(PROMPT[:3], 3)]:
                h.result(timeout=120)
        finally:
            eng.close()
    return {"spans": made.spans(), "main": threading.get_ident(),
            "params": len(net.collect_params())}


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_a_start_records_every_phase_nested_as_stated(start):
    spans, main = start["spans"], start["main"]
    ids = {s["id"]: s for s in spans}
    # a block's initialise and cast are one span each, however deep the
    # block: the outermost call times it
    for name in ("initialize", "cast"):
        outer = [s for s in named(spans, name) if s["parent"] is None]
        assert len(named(spans, name)) == len(outer) >= 1
    assert len(named(spans, "initialize")) == 2    # the net, the head
    assert len(named(spans, "cast")) == 1
    assert len(named(spans, "deferred_init")) == 1  # the head's weight
    assert len(named(spans, "set_data")) == start["params"]
    engine, = named(spans, "engine")
    programs, = named(spans, "programs")
    assert programs["parent"] == engine["id"] and engine["parent"] is None
    assert engine["t0"] <= programs["t0"] <= programs["t1"] <= engine["t1"]
    for s in spans:
        if not s["name"].startswith(("compile.", "first_call.")):
            assert s["tid"] == main
    for kind in ("prefill_chunk", "step"):
        first, = named(spans, "first_call." + kind)
        assert first["parent"] is None and first["tid"] != main
        assert first["thread"] == "mxtpu-serving-scheduler"
        assert first["t0"] >= engine["t1"]
        # the program is traced, lowered and compiled inside its first call
        kids = {s["name"] for s in spans if s["parent"] == first["id"]
                and s["fun_name"].endswith("serving_" + kind + ")")}
        assert kids == {"compile.lower", "compile.backend"}
        assert any(s["parent"] == first["id"]
                   and s["fun_name"] == "serving_" + kind
                   for s in named(spans, "compile.trace"))
    # compile spans sit in the phase their thread had open
    for s in spans:
        if s["name"].startswith("compile.") and s["parent"] is not None:
            p = ids[s["parent"]]
            assert p["tid"] == s["tid"]
            assert p["t0"] - 1e-3 <= s["t0"] <= s["t1"] <= p["t1"] + 1e-3
    # a top-level phase notes the device's memory where it reports any
    assert all("bytes_in_use" not in s for s in spans if s["parent"])


def test_self_times_add_up_to_no_more_than_the_wall(start):
    spans = start["spans"]
    for tid in {s["tid"] for s in spans}:
        mine = [s for s in spans if s["tid"] == tid]
        phases = [s for s in mine if not s["name"].startswith("compile.")]
        compiled = union_measure((s["t0"], s["t1"]) for s in mine
                                 if s["name"].startswith("compile."))
        wall = max(s["t1"] for s in mine) - min(s["t0"] for s in mine)
        selves = [self_time(s, mine) for s in phases]
        assert all(x >= -1e-6 for x in selves)
        assert sum(selves) + compiled <= wall + 1e-6


def test_with_telemetry_off_each_serving_program_is_lowered_once(start):
    lowered = {}
    for s in named(start["spans"], "compile.lower"):
        lowered[s["fun_name"]] = lowered.get(s["fun_name"], 0) + 1
    assert lowered["jit(serving_step)"] == 1
    assert lowered["jit(serving_prefill_chunk)"] == 1


def test_a_fresh_jit_is_traced_lowered_and_compiled_inside_the_open_phase(
        record):
    def setup_spans_probe(x):
        return x * 3 + 1

    with profiler.setup_phase("outer", why="probe"):
        jax.jit(setup_spans_probe)(jnp.arange(5)).block_until_ready()
    spans = record.spans()
    outer, = named(spans, "outer")
    assert outer["why"] == "probe" and outer["parent"] is None
    mine = [s for s in spans if s["parent"] == outer["id"]]
    got = {(s["name"], s["fun_name"]) for s in mine}
    assert {("compile.trace", "setup_spans_probe"),
            ("compile.lower", "jit(setup_spans_probe)"),
            ("compile.backend", "jit(setup_spans_probe)")} <= got
    for s in mine:
        assert s["tid"] == threading.get_ident()
        assert outer["t0"] - 1e-3 <= s["t0"] <= s["t1"] <= outer["t1"] + 1e-3
    backend, = [s for s in mine if s["name"] == "compile.backend"
                and s["fun_name"] == "jit(setup_spans_probe)"]
    assert backend["hits"] + backend["misses"] <= 1


def test_a_phase_inside_itself_is_the_outer_ones(record):
    with profiler.setup_phase("cast"):
        with profiler.setup_phase("cast"):
            with profiler.setup_phase("set_data"):
                pass
    cast, = named(record.spans(), "cast")
    inner, = named(record.spans(), "set_data")
    assert inner["parent"] == cast["id"]


def test_the_record_keeps_the_first_spans_within_its_bound(record,
                                                            monkeypatch):
    small = profiler.SetupRecord(cap=5)
    monkeypatch.setattr(profiler, "_setup", small)
    for i in range(8):
        with profiler.setup_phase(f"p{i}"):
            pass
    assert [s["name"] for s in profiler.setup_spans()] == [
        f"p{i}" for i in range(5)]
    assert profiler.setup_spans_dropped() == 3
    assert len(profiler._setup.spans()) <= small.cap


def test_threads_that_record_at_once_lose_no_span(record, monkeypatch):
    """More threads than cores, switching often: every span is kept or
    counted as dropped, each nested in its own thread's phase."""
    import os
    import sys

    threads, each = 2 * (os.cpu_count() or 4), 200
    small = profiler.SetupRecord(cap=threads * each // 2)    # half kept
    monkeypatch.setattr(profiler, "_setup", small)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each // 2):
                with profiler.setup_phase("outer"):
                    with profiler.setup_phase("inner"):
                        pass

        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    spans = small.spans()
    assert len(spans) == small.cap and small.dropped == threads * each // 2
    ids = {s["id"]: s for s in spans}
    assert len(ids) == len(spans)
    for s in spans:
        if s["name"] == "outer":
            assert s["parent"] is None
        elif s["parent"] in ids:           # its outer span may be dropped
            outer = ids[s["parent"]]
            assert outer["name"] == "outer" and outer["tid"] == s["tid"]
            assert outer["t0"] <= s["t0"] <= s["t1"] <= outer["t1"]


def test_the_processs_record_holds_the_packages_import():
    spans = profiler._setup.spans()
    assert len(spans) <= profiler._setup.cap == profiler.DEFAULT_SETUP_SPANS
    imp = named(spans, "import")
    assert len(imp) == 1 and imp[0]["parent"] is None
    assert imp[0]["t1"] > imp[0]["t0"]


def test_the_kill_switch_records_nothing(record, monkeypatch):
    monkeypatch.setenv("MXTPU_SERVING_PROFILER", "0")

    def setup_spans_off(x):
        return x - 2

    with profiler.setup_phase("engine"):
        jax.jit(setup_spans_off)(jnp.arange(3)).block_until_ready()
    profiler.record_setup_span("import", 1.0, 2.0)
    assert record.spans() == [] and record.dropped == 0


def test_a_phase_stands_in_a_jax_profiler_trace(record, tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with profiler.setup_phase("traced_probe"):
            jnp.ones(3).block_until_ready()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events}
    assert "setup.traced_probe" in names
    assert named(record.spans(), "traced_probe")
