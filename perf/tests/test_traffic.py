import json
import os

import numpy as np
import pytest

from perf import run

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(run.HERE, "traffic"))
               if f.endswith(".json"))
# (prompt, output) of `shape_seed` 27's block: means 173.9 and 207.3
SERVE_BATCH_BLOCK = [
    (33, 68), (45, 297), (53, 362), (56, 125), (72, 287), (74, 149),
    (76, 108), (78, 154), (86, 126), (98, 79), (221, 277), (286, 365),
    (348, 423), (396, 66), (423, 225), (438, 206)]
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [(w["name"], w["traffic"]) for w in json.load(_f)["workloads"]]


def _load(mix):
    m = run.load_json("traffic", mix + ".json")
    return m, run.load_file("traffic", m["generator"])


def _same(a, b):
    return (len(a) == len(b) and all(
        x["max_new"] == y["max_new"]
        and np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b)))


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests_other_seed_differs(mix):
    m, generate = _load(mix)
    big = 2**31 + 12345          # the driver's seeds pass 32 signed bits
    a, b = generate.requests(m, big, 50257), generate.requests(m, big, 50257)
    c = generate.requests(m, big + 1, 50257)
    assert _same(a, b)
    assert not _same(a, c)
    assert a[0]["prompt"].dtype == np.int32
    assert all(0 <= int(r["prompt"].min()) and int(r["prompt"].max()) < 50257
               for r in a[:50])


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_holds_the_same_sizes_within_the_limit(mix):
    m, generate = _load(mix)
    a, c = generate.requests(m, 1, 50257), generate.requests(m, 2, 50257)
    assert len(a) == m["count"]
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    assert sizes(a) == sizes(c)                  # same work, another order
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
    assert all(len(r["prompt"]) + r["max_new"] <= m["max_total"]
               and r["max_new"] >= 1 for r in a)
    lo, hi = m["prompt_len"]["lo"], m["prompt_len"]["hi"]
    assert all(lo <= len(r["prompt"]) <= hi for r in a)
    assert all(r["max_new"] <= m["output_len"]["hi"] for r in a)


@pytest.mark.parametrize("mix", MIXES)
def test_blocks_hold_the_same_sizes_in_an_order_of_their_own(mix):
    m, generate = _load(mix)
    rs = generate.requests(m, 77, 50257)
    n = m["block"]
    blocks = [[(len(r["prompt"]), r["max_new"]) for r in rs[i:i + n]]
              for i in range(0, len(rs), n)]
    assert len(blocks) == m["count"] // n
    assert all(sorted(b) == sorted(blocks[0]) for b in blocks)
    assert any(b != blocks[0] for b in blocks)


def test_clipping_to_max_total_and_a_block_that_does_not_divide():
    generate = run.load_file("traffic", "backlog")
    m = {"generator": "backlog", "count": 50, "block": 25, "shape_seed": 1,
         "max_total": 40, "prompt_len": {"lo": 8, "hi": 32},
         "output_len": {"lo": 30, "hi": 30}}
    rs = generate.requests(m, 5, 100)
    assert all(len(r["prompt"]) + r["max_new"] <= 40 for r in rs)
    assert any(r["max_new"] < 30 for r in rs)
    with pytest.raises(ValueError, match="does not divide"):
        generate.requests(dict(m, block=7), 5, 100)


@pytest.mark.parametrize("cell, mix", CELLS)
def test_a_backlog_holds_four_times_what_a_traced_run_consumes(cell, mix):
    """A closed backlog may not run out: the driver ends such a run with
    exit code 1.  The cell's file says how many requests a traced run took
    off the queue by its last reading when it was last read on the chip
    (every window line prints `queue_depth_last`); whoever reads a faster
    program writes the new number there, and this says whether `count`
    still holds four times that, before a check does."""
    m = run.load_json("traffic", mix + ".json")
    if m["generator"] != "backlog":
        pytest.skip("not a closed backlog")
    used = run.load_json("workloads", cell + ".json")[
        "consumed_by_a_traced_run"]
    assert used["origin"]
    assert m["count"] >= 4 * used["requests"] > 0


def test_the_sizes_a_run_of_serve_batch_draws():
    """`shape_seed` 27's block of 16: what every window of
    `gpt2-medium.serve-batch` has held since PR 27, whatever `count` is."""
    m, generate = _load("serve-batch")
    rs = generate.requests(m, 3, 50257)
    assert len(rs) == 7680
    sizes = sorted((len(r["prompt"]), r["max_new"]) for r in rs[:16])
    assert sizes == SERVE_BATCH_BLOCK
    assert sorted((len(r["prompt"]), r["max_new"])
                  for r in rs[-16:]) == SERVE_BATCH_BLOCK
    assert sum(p + n for p, n in sizes) / 16 == pytest.approx(381.2, abs=0.1)
