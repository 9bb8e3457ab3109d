"""The reduction from a trace to numbers, on small traces whose values are
checked by hand.  `data/recorded_*.json` are excerpts of real traces from
the chip, saved by a traced run with PERF_TRACE_EXCERPT=<file> set, in
`read_xplane`'s form."""
import glob
import json
import os

import pytest

from perf import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def planes():
    """One device, window [10, 20): ops at [9,11) [11,12) [14,16) [15,17)
    and one after the window; the host was in `step` for [12,14) (with
    `fetch` inside it for [12.5,14)) and in nothing for [17,20)."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [("fusion.1", "matmul", 9.0, 2.0),
                    ("custom-call.2", "pallas_call xent", 11.0, 1.0),
                    ("fusion.1", "matmul", 14.0, 2.0),
                    ("copy.3", "", 15.0, 2.0),
                    ("fusion.1", "matmul", 21.0, 1.0)],
            "modules": [("jit_step(1)", "", 9.0, 3.0),
                        ("jit_step(1)", "", 14.0, 3.0),
                        ("jit_other(2)", "", 19.5, 2.0)]}},
        "host": [(tr.WINDOW_SPAN, "main", 10.0, 10.0),
                 ("step", "main", 12.0, 2.0),
                 ("fetch", "main", 12.5, 1.5),
                 ("elsewhere", "other", 0.0, 5.0)],
    }


def test_union_and_gaps():
    assert tr.union([(3, 4), (1, 2), (1.5, 3.5), (6, 7)]) == [[1, 4], [6, 7]]
    assert tr.gaps([[1, 4], [6, 7]], 0, 8) == [(0, 1), (4, 6), (7, 8)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def test_busy_idle_ops_and_modules_by_hand():
    r = tr.reduce(planes(), chips=1)
    assert r["window_s"] == 10.0
    # clipped to the window: [10,11) + [11,12) + [14,17) = 5 s busy
    assert r["busy_s"] == pytest.approx(5.0)
    # fusion.1: 1 s of the first (clipped) + 2 s; the one after is out
    assert r["ops"]["fusion.1"]["seconds"] == pytest.approx(3.0)
    assert r["ops"]["fusion.1"]["count"] == 2
    assert r["ops"]["custom-call.2"]["label"] == "pallas_call xent"
    assert r["modules"]["jit_step(1)"] == {"count": 2, "seconds":
                                           pytest.approx(5.0)}
    assert r["modules"]["jit_other(2)"]["seconds"] == pytest.approx(0.5)
    # idle [12,14): `step` covers all of it, `fetch` only 1.5 s -> step;
    # idle [17,20): no host span
    assert dict(map(tuple, r["breakdown"]["idle_gaps"])) == {
        "step": pytest.approx(2.0), "idle": pytest.approx(3.0)}
    # the breakdown goes by kind and result shape, numbered siblings summed
    assert r["breakdown"]["device_ops"][0] == ["fusion",
                                               pytest.approx(3.0)]
    assert tr.family("copy.301", "bf16[2049,16,16,64]{3,1,2,0:T(8,128)} "
                     "copy(bf16[2049,16,16,64]{0,3,2,1} %pool_v_3_.1)") \
        == "copy bf16[2049,16,16,64]"
    assert tr.family("fusion.2919", "(bf16[30522,1024]{1,0}, f32[30522,1024]"
                     "{1,0}) fusion(...)") == "fusion bf16[30522,1024]"
    assert tr.family("_paged_core.47", "") == "_paged_core"
    assert tr.ops_matching(r, "pallas_call", "xent") == {
        "seconds": pytest.approx(1.0), "count": 1}
    assert tr.ops_matching(r, "no such kernel") is None


def test_innermost_host_span_wins_when_both_cover_the_gap():
    host = [("outer", "t", 0.0, 10.0), ("inner", "t", 2.0, 3.0)]
    assert tr.attribute(host, [(2.5, 4.0), (6.0, 7.0), (11.0, 12.0)]) == [
        "inner", "outer", "idle"]


def test_two_devices_are_averaged():
    p = planes()
    p["devices"]["/device:TPU:1"] = {"ops": [("fusion.1", "", 10.0, 1.0)],
                                     "modules": []}
    r = tr.reduce(p, chips=2)
    assert r["busy_s"] == pytest.approx((5.0 + 1.0) / 2)
    assert tr.reduce(p, chips=1)["busy_s"] == pytest.approx(5.0)


def test_a_trace_with_nothing_on_the_device_is_refused():
    p = planes()
    p["devices"]["/device:TPU:0"]["ops"] = []
    with pytest.raises(RuntimeError, match="no operation ran"):
        tr.reduce(p, chips=1)
    del p["host"][0]
    with pytest.raises(RuntimeError, match="perf_window"):
        tr.reduce(p, chips=1)


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "recorded_*.json"))) or [None])
def test_recorded_excerpt_against_a_brute_force_count(path):
    """A real trace from the chip (an excerpt of some milliseconds, saved
    through PERF_TRACE_EXCERPT): the busy union against a count on a fine
    grid of instants, per-operation time against plain sums."""
    if path is None:
        pytest.skip("no recorded excerpt in perf/tests/data")
    with open(path) as f:
        p = json.load(f)["planes"]
    r = tr.reduce(p, chips=1)
    lo, span = next((h[2], h[3]) for h in p["host"] if h[0] == tr.WINDOW_SPAN)
    assert r["window_s"] == pytest.approx(span)
    ops = p["devices"][sorted(p["devices"])[0]]["ops"]
    assert len(ops) > 100
    n = 20000
    step = span / n
    hit = [False] * n
    for _name, _label, s, d in ops:
        a = max(0, int((s - lo) / step + 0.5))
        b = min(n, int((s + d - lo) / step + 0.5))
        for i in range(a, b):
            hit[i] = True
    assert r["busy_s"] == pytest.approx(sum(hit) * step, rel=5e-3)
    assert 0 < r["busy_s"] <= r["window_s"]
    sums = {}
    for name, _label, s, d in ops:
        sums[name] = sums.get(name, 0.0) + min(s + d, lo + span) - s
    for name, op in r["ops"].items():
        assert op["seconds"] == pytest.approx(sums[name])
    idle = sum(v for _k, v in r["breakdown"]["idle_gaps"])
    if len(r["breakdown"]["idle_gaps"]) < 10:   # the table holds them all
        assert idle == pytest.approx(span - r["busy_s"], rel=1e-6)
