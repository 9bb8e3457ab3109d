"""The five readers of the program's set-up record (`perf/metrics/
setup_lower_s.py`, `setup_compile_s.py`, `setup_params_s.py`,
`setup_engine_s.py`, `setup_warmup_s.py`, all through `perf/work/
setup.py`) on a record made by hand, and in a whole traced rehearsal."""
import importlib.util
import json
import os

import pytest

from incubator_mxnet_tpu.telemetry import profiler
from perf.tests import rehearse
from perf.work import setup

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = {"setup_lower_s": "lower", "setup_compile_s": "compile",
       "setup_params_s": "params", "setup_engine_s": "engine",
       "setup_warmup_s": "warmup"}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "..", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


MAIN, SCHED = 1, 2


def span(i, name, t0, t1, parent=None, tid=MAIN, **kw):
    return dict(kw, id=i, name=name, t0=t0, t1=t1, parent=parent, tid=tid,
                thread="main" if tid == MAIN else "scheduler")


# a start of [100, 200]: what each phase places is in the comment beside it
SPANS = [
    span(1, "import", 95.0, 105.0),                          # 5 of it inside
    span(2, "initialize", 110.0, 120.0),                     # params 4
    span(3, "compile.trace", 112.0, 114.0, 2, fun_name="a"),  # lower 2
    span(4, "compile.lower", 114.0, 115.0, 2, fun_name="jit(a)"),  # lower 1
    span(5, "compile.backend", 115.0, 118.0, 2, fun_name="jit(a)",
         hits=1, misses=0),                                  # compile 3
    # a jit traced inside another's trace counts once: lower 6, not 8
    span(6, "compile.trace", 130.0, 136.0, fun_name="outer"),
    span(7, "compile.trace", 131.0, 133.0, fun_name="inner"),
    span(8, "set_data", 140.0, 141.0),                       # params 1
    span(9, "set_data", 141.0, 142.0),                       # params 1
    span(10, "engine", 150.0, 160.0),                        # engine 3
    span(11, "programs", 151.0, 158.0, 10),                  # engine 5
    span(12, "compile.backend", 152.0, 154.0, 11, fun_name="jit(b)",
         hits=0, misses=1),                                  # compile 2
    # the scheduler's first calls: the warm-up wave runs from 165 to 200
    span(13, "first_call.prefill_chunk", 165.0, 180.0, tid=SCHED),
    span(14, "compile.trace", 166.0, 168.0, 13, SCHED,
         fun_name="serving_prefill_chunk"),                  # lower 2
    span(15, "compile.lower", 168.0, 170.0, 13, SCHED,
         fun_name="jit(serving_prefill_chunk)"),             # lower 2
    span(16, "compile.backend", 170.0, 176.0, 13, SCHED,
         fun_name="jit(serving_prefill_chunk)", hits=1, misses=0),  # 6
    # another thread's compile under the scheduler's counts once
    span(17, "compile.backend", 171.0, 174.0, fun_name="jit(c)", hits=1,
         misses=0),
    span(18, "first_call.step", 182.0, 190.0, tid=SCHED),
    span(19, "compile.backend", 183.0, 185.0, 18, SCHED,
         fun_name="jit(serving_step)", hits=1, misses=0),   # compile 2
    # the check's compile, after the window opened: not set-up's
    span(20, "compile.backend", 205.0, 210.0, fun_name="jit(ref)"),
]
WANT = {"lower": 2 + 1 + 6 + 2 + 2, "compile": 3 + 2 + 6 + 2,
        "params": 4 + 1 + 1, "engine": 3 + 5, "warmup": 35 - 4 - 8}


@pytest.fixture
def made(monkeypatch):
    rec = profiler.SetupRecord()
    monkeypatch.setattr(profiler, "_setup", rec)
    for s in SPANS:
        rec.add(s)
    return rec


def start():
    return {"t_open": 200.0, "setup_s": 100.0}


def test_each_instant_of_the_start_is_placed_once(made, capsys):
    rec = start()
    got = {name: reader(name)(rec) for name in NEW}
    assert got == {name: pytest.approx(WANT[p]) for name, p in NEW.items()}
    line, = [json.loads(x)["setup_phases"] for x in
             capsys.readouterr().out.splitlines()]     # printed once
    # what no span places: 100 - (10 + 6 + 2 + 10 + 35), the import in it
    assert line["unplaced_s"] == pytest.approx(37.0)
    assert line["import_s"] == pytest.approx(5.0)
    assert sum(got.values()) + line["unplaced_s"] == pytest.approx(
        rec["setup_s"], rel=0.01)
    assert line["spans"] == len(SPANS) - 1 and line["dropped"] == 0


def test_the_detail_line_places_compile_by_phase_and_program(made):
    rec = start()
    reader("setup_compile_s")(rec)
    out = rec["setup_phases"]
    by = out["compile_by_phase"]
    assert by["initialize"] == {"lower_s": pytest.approx(3.0),
                                "compile_s": pytest.approx(3.0)}
    assert by["first_call.prefill_chunk"] == {
        "lower_s": pytest.approx(4.0), "compile_s": pytest.approx(6.0)}
    assert by["programs"]["compile_s"] == pytest.approx(2.0)
    assert by["-"] == {"lower_s": pytest.approx(6.0),
                       "compile_s": pytest.approx(3.0)}
    progs = {p["name"]: p for p in out["programs"]["top"]}
    assert progs["serving_prefill_chunk"] == dict(
        name="serving_prefill_chunk", seconds=pytest.approx(10.0), traced=1,
        lowered=1, compiled=1, hits=1, misses=0)
    assert "ref" not in progs                  # outside the start
    assert out["programs"]["serving_lowered"] == {
        "serving_prefill_chunk": 1, "serving_step": 0}


def test_self_time_takes_out_children_of_any_kind():
    spans = [span(1, "engine", 0.0, 10.0), span(2, "programs", 1.0, 9.0, 1),
             span(3, "set_data", 2.0, 3.0, 2),
             span(4, "compile.backend", 4.0, 6.0, 2, fun_name="jit(x)")]
    out = setup.partition(spans, 0.0, 10.0)
    # the hand-over inside the engine is the parameters', the compile
    # the compile's: the engine keeps 2 + 5
    assert out["seconds"] == {
        "lower": 0.0, "compile": pytest.approx(2.0),
        "params": pytest.approx(1.0), "engine": pytest.approx(7.0),
        "warmup": 0.0}
    assert out["unplaced_s"] == 0.0


def test_intervals():
    assert setup.union([(3, 4), (0, 2), (1, 3), (5, 5)]) == [(0, 4)]
    assert setup.minus([(0, 10), (12, 14)], [(1, 2), (3, 13)]) == [
        (0, 1), (2, 3), (13, 14)]
    assert setup.measure([(0, 1), (2, 4.5)]) == 3.5


@pytest.mark.parametrize("name", sorted(NEW))
def test_no_record_or_an_empty_one_says_nothing(monkeypatch, capsys, name):
    monkeypatch.setattr(profiler, "_setup", profiler.SetupRecord())
    assert reader(name)(start()) is None               # switched off
    assert "MXTPU_SERVING_PROFILER" in capsys.readouterr().out
    monkeypatch.delattr(profiler, "setup_spans")       # an older program
    assert reader(name)(start()) is None
    assert "no set-up record" in capsys.readouterr().out


def test_a_traced_rehearsal_reports_them(capsys):
    result = rehearse.run_tiny("gpt2-medium.serve-batch", seed=2**31 + 41,
                               seconds=1.5, trace=1)
    assert result["correct"]
    got = {k: result["metrics"][k]["value"] for k in NEW}
    assert all(v > 0 for v in got.values()), got
    line, = [json.loads(x)["setup_phases"] for x in
             capsys.readouterr().out.splitlines() if '"setup_phases"' in x]
    assert sum(got.values()) + line["unplaced_s"] == pytest.approx(
        line["setup_s"], rel=0.01)
    assert line["programs"]["serving_lowered"] == {
        "serving_prefill_chunk": 1, "serving_step": 1}
