"""FLOPs, byte counts and the count of what a span of a served run held,
against hand arithmetic, at one tiny and one published size."""
import pytest

from perf import run
from perf.work import flops, paged, served

GPT2 = run.load_json("configs", "gpt2-medium.json")
PEAK = run.load_json("peaks.json")["devices"]["TPU v5 lite"]


def test_gpt2_medium_serve_flops():
    # layers 24 * (4*1024^2 + 2*1024*4096) = 301,989,888; head 50257*1024
    # = 51,463,168 (353,453,056 matmul parameters together)
    work = dict(prompt_tokens=100, decode_tokens=10, output_tokens=11,
                prefill_context=5050, decode_context=1045)
    want = (2 * 301_989_888 * 110 + 2 * 51_463_168 * 11
            + 4 * 24 * 1024 * 6095)
    assert flops.serve_flops(GPT2, work) == want
    tiny = dict(n_embd=8, n_inner=16, n_layer=2, vocab_size=10)
    work = dict(prompt_tokens=3, decode_tokens=2, output_tokens=3,
                prefill_context=6, decode_context=9)
    assert flops.serve_flops(tiny, work) == (2 * 1024 * 5 + 2 * 80 * 3
                                             + 4 * 2 * 8 * 15)


def test_paged_count_depends_on_positions_only():
    # one lane at pos 511 attends 512 positions: K and V, 1024 values of
    # 2 B, 24 layers = 512 * 2 * 1024 * 2 * 24
    assert paged.bytes_attended(512, GPT2) == 50_331_648
    # 32 lanes at 512: 1.61 GB a step -> 1.97 ms at 819 GB/s; their 32
    # queries' products (4 * 1024 * 24 a position) are far under that
    assert paged.floor_seconds(32 * 512, 32 * 512, GPT2, PEAK) == \
        pytest.approx(32 * 50_331_648 / 819e9)
    tiny = dict(n_embd=8, n_layer=2)
    assert paged.bytes_attended(5, tiny) == 2 * 5 * 8 * 2 * 2
    assert paged.flops_attended(5, tiny) == 4 * 5 * 8 * 2
    # where the products need longer than the read, they are the floor
    slow_mem = dict(hbm_bytes_per_s=1e18, flops_bf16=1e3)
    assert paged.floor_seconds(5, 7, tiny, slow_mem) == pytest.approx(
        4 * 7 * 8 * 2 / 1e3)
    # nothing of the block size, the table or the kernel enters
    assert set(paged.bytes_attended.__code__.co_varnames) == {
        "positions", "cfg", "itemsize"}


def test_a_chunk_reads_its_context_once_not_once_a_query():
    # a prompt of 70 in chunks of 32, admitted at 10.0, first token at 13.0:
    # chunks end at 11, 12, 13 holding positions [0,32) [32,64) [64,70)
    req = (70, 10.0, [13.0, 13.5, 14.0])
    assert served.chunk_ends([req], 32) == [
        (pytest.approx(11.0), 0, 32), (pytest.approx(12.0), 32, 64),
        (pytest.approx(13.0), 64, 70)]
    w = served.count_work([req], 11.5, 13.2, 32)
    # the chunks ending at 12 and 13 are inside: 32 + 6 prompt tokens;
    # their reads are 64 + 70 positions (not 33 + ... + 70 = 1957)
    assert (w["prompt_tokens"], w["chunks"], w["chunk_context"]) == (
        38, 2, 134)
    # their 38 queries attend 33 + 34 + ... + 70 positions
    assert w["prefill_context"] == sum(range(33, 71)) == 1957
    # one output inside (the first, at 13.0: the last chunk yields it)
    assert (w["output_tokens"], w["decode_tokens"]) == (1, 0)
    w = served.count_work([req], 13.2, 20.0, 32)
    # two decode steps' tokens: outputs 1 and 2 attend 71 and 72 positions
    assert (w["output_tokens"], w["decode_tokens"], w["decode_context"]) \
        == (2, 2, 143)
    assert w["gaps"] == [pytest.approx(0.5), pytest.approx(0.5)]
    assert w["prompt_tokens"] == 0
    # bytes: 134 positions * K and V * 8 values * 2 B * 2 layers
    tiny = dict(n_embd=8, n_layer=2)
    assert paged.bytes_attended(134, tiny) == 134 * 2 * 8 * 2 * 2


def test_prompts_are_prefilled_one_after_another():
    # both admitted at 0; the first's first token at 2.0, the second's at
    # 3.0: the second's two chunks run after 2.0, ending at 2.5 and 3.0
    a, b = (32, 0.0, [2.0]), (40, 0.0, [3.0])
    assert served.chunk_ends([b, a], 32) == [
        (pytest.approx(2.0), 0, 32), (pytest.approx(2.5), 0, 32),
        (pytest.approx(3.0), 32, 40)]
    # a request still queued, or admitted with no first token yet, has
    # nothing to count
    assert served.chunk_ends([(9, None, []), (9, 1.0, [])], 32) == []
    # every prompt token is counted once, whatever the span's cut
    whole = served.count_work([a, b], 0.0, 9.0, 32)
    parts = [served.count_work([a, b], lo, hi, 32)
             for lo, hi in ((0.0, 2.2), (2.2, 2.7), (2.7, 9.0))]
    assert whole["prompt_tokens"] == 72 == sum(p["prompt_tokens"]
                                               for p in parts)
    assert whole["chunk_context"] == 32 + 32 + 40
