"""The hybrid configuration's part of the yardstick: `perf/work/hybrid.py`'s
counts against hand arithmetic and against the reference's own leaves, the
control of `correct` at a test's size, the CPU rehearsal of the cell, and
the three readers on a recorded excerpt of a real traced run."""
import json
import math
import os

import numpy as np
import pytest

from perf import compare, run, trace_reduce
from perf.tests import rehearse
from perf.work import hybrid

CELL = "jamba2-3b.serve-docs"
JAMBA = run.load_json("configs", "jamba2-3b.json")
PEAK = run.load_json("peaks.json")["devices"]["TPU v5 lite"]
TINY = dict(hidden_size=8, intermediate_size=16, vocab_size=10,
            num_hidden_layers=4, attn_layer_period=4, attn_layer_offset=1,
            num_attention_heads=2, num_key_value_heads=1, mamba_expand=2,
            mamba_d_state=4, mamba_d_conv=4, mamba_dt_rank=3,
            mamba_conv_bias=True, mamba_proj_bias=False)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_counts_at_a_tiny_size_by_hand():
    assert hybrid.layer_counts(TINY) == {"attn": 1, "ssm": 3, "all": 4}
    p = hybrid.matmul_params(TINY)
    # mlp: gate, up, down 3 x 16 x 8; attention: q 8x8, k and v 4x8 each,
    # o 8x8; mamba: in 32x8, x_proj (3+8)x16, dt_proj 16x3, out 8x16
    assert p == {"mlp": 384, "attn": 64 + 32 + 32 + 64,
                 "ssm": 256 + 176 + 48 + 128}
    # a scan: 16 channels x (7 x 4 state elements + 8)
    assert hybrid.scan_ops_per_token(TINY) == 16 * 36
    work = dict(prompt_tokens=3, decode_tokens=2, output_tokens=3,
                prefill_context=6, decode_context=9)
    per_token = 2 * (4 * 384 + 192 + 3 * 608) + 3 * 576
    assert hybrid.serve_flops(TINY, work) == (
        per_token * 5 + 2 * 10 * 8 * 3 + 4 * 1 * 2 * 4 * 15)
    # the scan's bytes: 3 layers; a lane's state read and written
    # 2 x 4 x 16 x 4 B; a token's streams 16 x 10 B + 2 x 4 x 4 B
    assert hybrid.scan_bytes(TINY, tokens=7, programs_lanes=2) == 3 * (
        2 * 512 + 7 * 192)
    # K and V by KV heads: 5 positions x 2 x 1 head x 4 x 2 B x 1 layer
    assert hybrid.paged_bytes(5, TINY) == 80
    assert hybrid.paged_flops(5, TINY) == 4 * 5 * 2 * 4
    slow_mem = dict(hbm_bytes_per_s=1e18, flops_bf16=1e3)
    assert hybrid.paged_floor_seconds(5, 7, TINY, slow_mem) == \
        pytest.approx(4 * 7 * 8 / 1e3)
    assert hybrid.scan_floor_seconds(TINY, 7, 2, slow_mem) == \
        pytest.approx(3 * 576 * 7 / 1e3)
    # nothing of a block size, a chunk or a kernel's tiling enters
    assert set(hybrid.scan_bytes.__code__.co_varnames[:3]) == {
        "cfg", "tokens", "programs_lanes"}


def test_counts_agree_with_the_references_leaves():
    """Every matrix of `param_shapes` is counted once, by its layer's
    kind; what is left over is the embedding (a gather, and the head),
    the norms' gains, biases, the conv's taps, A_log and D."""
    ref = run.load_file("reference", "jamba2-3b")
    shapes = ref.param_shapes(JAMBA)
    n, p = hybrid.layer_counts(JAMBA), hybrid.matmul_params(JAMBA)
    assert (n["attn"], n["ssm"]) == (2, 26)
    assert [i for i, k in enumerate(ref.layer_kinds(JAMBA)) if k == "attn"] \
        == [7, 21]
    matrices = {k: math.prod(v) for k, v in shapes.items()
                if k.endswith("_w") and k != "ssm.conv_w"}
    by = lambda prefix: sum(v for k, v in matrices.items()
                            if k.startswith(prefix))
    assert by("layers.") == n["all"] * p["mlp"] == 28 * 62_914_560
    assert by("attn.") == n["attn"] * p["attn"] == 2 * 13_762_560
    assert by("ssm.") == n["ssm"] * p["ssm"] == 26 * 41_123_840
    total = sum(math.prod(v) for v in shapes.values())
    assert total == 3_029_337_472              # 6.06 GB in bfloat16
    # one decode token: 2 a matmul parameter, the scans, the tied head
    work = dict(prompt_tokens=0, decode_tokens=1, output_tokens=1,
                prefill_context=0, decode_context=1000)
    want = (2 * (by("layers.") + by("attn.") + by("ssm."))
            + 26 * 5120 * (7 * 16 + 8) + 2 * 65536 * 2560
            + 4 * 2 * 20 * 128 * 1000)
    assert hybrid.serve_flops(JAMBA, work) == want
    # a step of 64 lanes moves 26 x 64 x 2 x 5120 x 16 x 4 B = 1.09 GB of
    # state: 1.33 ms at 819 GB/s, its streams 0.1 ms more
    assert hybrid.scan_bytes(JAMBA, 64, 64) == 26 * (
        64 * 655_360 + 64 * (51_200 + 128))
    assert 1.3e-3 < hybrid.scan_floor_seconds(JAMBA, 64, 64, PEAK) < 1.5e-3


def test_kernels_are_found_by_name_alone():
    reduced = {"ops": {
        "selective_scan.3": {"seconds": 1.0, "count": 2, "label": "f32[..."},
        "selective_scan": {"seconds": 0.5, "count": 1, "label": ""},
        "selective_scan_bwd.1": {"seconds": 9.0, "count": 9, "label": ""},
        "fusion.7": {"seconds": 9.0, "count": 9,
                     "label": "op_name=ssm_scan/selective_scan"},
        "paged_attention.12": {"seconds": 2.0, "count": 4, "label": ""},
        "paged_attention_q8.1": {"seconds": 9.0, "count": 9, "label": ""}},
        "modules": {"jit_serving_step(1)": {"count": 3, "seconds": 1.0},
                    "jit_serving_prefill_chunk(2)": {"count": 2,
                                                     "seconds": 1.0}}}
    assert hybrid.kernel_time(reduced, "selective_scan") == {
        "seconds": 1.5, "count": 3}
    assert hybrid.kernel_time(reduced, "paged_attention") == {
        "seconds": 2.0, "count": 4}
    assert hybrid.kernel_time(reduced, "flash_fwd") is None
    assert hybrid.programs_run(reduced, "jit_serving_step(") == 3


# what a test run can hold of the cell: an eighth of the vocabulary, three
# fifths of the width, five periods of a 4-layer pattern.  The control's
# gap grows with width and depth (5.5-5.7 at the cell's own size on the
# chip, PERF.md section 2; here 2.4-3.0; 0.5 at 8 layers of 512)
SMALL = dict(hidden_size=1536, intermediate_size=3072, vocab_size=8192,
             num_hidden_layers=20, attn_layer_period=4, attn_layer_offset=2,
             num_attention_heads=12, num_key_value_heads=1, mamba_dt_rank=96,
             max_position_embeddings=128)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_the_control_fails_and_the_stated_precision_passes(seed):
    """The fp8 reference in the program's place, held to the cell's limit:
    the token IT puts first lies further below the float32 reference's
    best than the limit; the token the bf16 reference (the precision the
    configuration states) puts first does not."""
    from perf import weights

    ref = run.load_file("reference", "jamba2-3b")
    cfg = dict(JAMBA, **SMALL)
    limits = run.load_json("workloads", CELL + ".json")["limits"]
    w = weights.make(seed, ref.param_shapes(cfg))
    rng = np.random.default_rng(seed)
    rows = [(rng.integers(0, cfg["vocab_size"], n, dtype=np.int32),
             rng.integers(0, cfg["vocab_size"], m, dtype=np.int32))
            for n, m in ((40, 30), (17, 60), (5, 90))]

    def widest(prec):
        gaps = [g for row in ref.served_gaps(w, rows, cfg, 128, control=prec)
                for g in row]
        assert len(gaps) == 30 + 60 + 90
        return compare.served(gaps, limits, {}, lambda *a: None)[0]

    fp8, bf16 = widest("fp8"), widest("bf16")
    assert fp8["value"] > fp8["limit"], fp8
    assert bf16["value"] <= bf16["limit"], bf16


def test_the_rehearsal_of_the_cell_is_correct():
    result = rehearse.run_tiny(CELL, seed=2**31 + 6, seconds=1.5, trace=1)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    # on the CPU no kernel runs, so the two rooflines say nothing; the
    # whole step's share and the nine readers that know no model do
    assert "step_mfu.serve.hybrid" in result["metrics"]
    assert "selective_scan_roofline" not in result["metrics"]
    assert {"batch_occupancy_pct", "sched_host_ms", "kv_pool_written_pct",
            "prefill_tokens_per_s"} <= set(result["metrics"])


def test_the_readers_leave_their_metric_out_where_nothing_is_to_read():
    """A program without the kernels (the parent commit has neither) gives
    None, not an error."""
    empty = {"ops": {"fusion.1": {"seconds": 1.0, "count": 1, "label": ""}},
             "modules": {}}
    record = dict(trace=empty, trace_t0=0.0, trace_t1=1.0, chunk=256,
                  requests=[], config=JAMBA, peak=PEAK)
    for name in ("selective_scan_roofline", "paged_attn_roofline.grouped"):
        assert run.load_file("metrics", name).read(record) is None
        assert run.load_file("metrics", name).read({}) is None
    assert run.load_file("metrics", "step_mfu.serve.hybrid").read({}) is None


def test_the_readers_on_a_recorded_excerpt_of_the_real_run():
    """35 ms of the cell's traced run on the chip (PERF_TRACE_EXCERPT, my
    chip run, PR 30, seed 3000600001): two decode steps and the tail of a
    prefill chunk.  The three kernels are found by their names, their time
    is the plain sum of their events, and each reader divides the floor
    `perf/work/hybrid.py` gives for the window's tokens by it."""
    with open(os.path.join(DATA, "recorded_hybrid.json")) as f:
        planes = json.load(f)["planes"]
    reduced = trace_reduce.reduce(planes, chips=1)
    ops = planes["devices"][sorted(planes["devices"])[0]]["ops"]

    def plain_sum(kernel):
        hit = [d for name, _l, _s, d in ops
               if name.split(".")[0] == kernel]
        return sum(hit), len(hit)

    scan_s, scan_n = plain_sum("selective_scan")
    step_s, step_n = plain_sum("paged_attention")
    win_s, win_n = plain_sum("paged_attention_window")
    assert scan_n == 59 and step_n == 3 and win_n == 2
    assert hybrid.kernel_time(reduced, "selective_scan")["seconds"] == \
        pytest.approx(scan_s)
    assert hybrid.programs_run(reduced, "jit_serving_step(") == 2
    families = [name for name, _s in reduced["breakdown"]["device_ops"]]
    assert any(f.startswith("selective_scan ") for f in families)

    # a window that held one prompt of 300 (chunks of 256 and 44) and two
    # decode tokens at contexts 301 and 302
    requests = [(300, 0.1, [0.5, 0.6, 0.7])]
    record = dict(trace=reduced, trace_t0=0.0, trace_t1=1.0, chunk=256,
                  requests=requests, config=JAMBA, peak=PEAK)
    # 59 calls beside 2 steps and no whole chunk: the step runs the kernel,
    # so decode tokens count: 302 tokens, 2 chunks + 2 lanes advanced
    want = 100 * hybrid.scan_floor_seconds(JAMBA, 302, 4, PEAK) / scan_s
    got = run.load_file("metrics", "selective_scan_roofline").read(record)
    assert got == pytest.approx(want)
    # reads: 256 + 300 by the chunks, 301 + 302 by the steps; products:
    # every prompt token its prefix, every decode token its context
    floor = hybrid.paged_floor_seconds(
        256 + 300 + 603, 300 * 301 // 2 + 603, JAMBA, PEAK)
    got = run.load_file("metrics", "paged_attn_roofline.grouped").read(record)
    # (an event that crosses the excerpt's end is clipped to it: the sum of
    # the clipped times is `trace_reduce`'s, checked in its own test)
    paged_s = sum(hybrid.kernel_time(reduced, k)["seconds"]
                  for k in ("paged_attention", "paged_attention_window"))
    assert paged_s <= step_s + win_s
    assert got == pytest.approx(100 * floor / paged_s)
