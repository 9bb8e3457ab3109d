"""The sparse configuration's part of the yardstick: `perf/work/sparse.py`'s
counts against hand arithmetic at the published widths and against the
reference's own leaves (853M parameters held), the configuration against
the catalog's row, the control of `correct` at a test's size ("recent"
among them), the CPU rehearsal of the cell, and the five readers where
nothing is to read and on counts by hand."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from perf import run
from perf.tests import rehearse
from perf.work import sparse

CELL = "keye-vl-2-30b-a3b.serve-longdocs"
KEYE = run.load_json("configs", "keye-vl-2-30b-a3b.json")
PEAK = run.load_json("peaks.json")["devices"]["TPU v5 lite"]
NEW = ("step_mfu.serve.sparse", "index_scores_roofline",
       "sparse_attn_roofline", "expert_load_imbalance.sparse",
       "sparse_selected_share")


def test_counts_at_the_published_widths_by_hand():
    # attention: q and o 32 x 128 x 2048 each, k and v 4 x 128 x 2048 each;
    # the index: 16 x 64 queries, one key of 64, 16 weights; the router 128
    assert sparse.layer_params(KEYE) == {
        "attention": 2 * 32 * 128 * 2048 + 2 * 4 * 128 * 2048,
        "indexer": (16 * 64 + 64 + 16) * 2048, "router": 128 * 2048}
    assert sparse.layer_params(KEYE) == {
        "attention": 18_874_368, "indexer": 2_260_992, "router": 262_144}
    assert sparse.pair_params(KEYE) == 3 * 2048 * 768 == 4_718_592
    # one decode token at a context of 12,000 with 2 pairs held here, in
    # each of 8 layers: 12,000 index scores of 2 x 16 x 64, attention over
    # 2,048 of 4 x 32 x 128, and the head
    work = dict(prompt_tokens=0, decode_tokens=1, output_tokens=1,
                prefill_context=0, decode_context=12000)
    picked = dict(prefill_context=0, decode_context=2048)
    assert sparse.serve_flops(KEYE, work, picked, 2) == (
        2 * 8 * (18_874_368 + 2_260_992 + 262_144) + 2 * 2 * 4_718_592
        + 2 * 18992 * 2048
        + 8 * (2 * 16 * 64 * 12000 + 4 * 32 * 128 * 2048))
    # the index's floor: 128 B of key a position, 8 layers; or 2,048
    # products a query and position
    slow_hbm = dict(hbm_bytes_per_s=1e3, flops_bf16=1e30)
    slow_mxu = dict(hbm_bytes_per_s=1e30, flops_bf16=1e3)
    work = dict(decode_context=1000, chunk_context=512, prefill_context=70000)
    assert sparse.index_floor_seconds(KEYE, work, slow_hbm) == \
        pytest.approx(8 * 1512 * 128 / 1e3)
    assert sparse.index_floor_seconds(KEYE, work, slow_mxu) == \
        pytest.approx(8 * 2 * 16 * 64 * 71000 / 1e3)
    # the attention's: K and V rows of 4 x 128 x 2 B each, 2 KB a position
    picked = dict(decode_context=2048, chunk_rows=512, prefill_context=9000)
    assert sparse.sparse_floor_seconds(KEYE, picked, slow_hbm) == \
        pytest.approx(8 * 2560 * 2048 / 1e3)
    assert sparse.sparse_floor_seconds(KEYE, picked, slow_mxu) == \
        pytest.approx(8 * 4 * 32 * 128 * 11048 / 1e3)


def test_selected_work_counts_what_the_selection_leaves():
    # one prompt of 3,000 in chunks of 512, first token at 0.6, then two
    # decode tokens; topk 2,048
    requests = [(3000, 0.0, [0.6, 0.7, 0.8])]
    got = sparse.selected_work(requests, 0.0, 1.0, 512, 2048)
    # decode tokens at contexts 3,001 and 3,002 attend 2,048 each
    assert got["decode_context"] == 2 * 2048
    # prompt token q attends min(q + 1, 2048)
    assert got["prefill_context"] == 2048 * 2049 // 2 + (3000 - 2048) * 2048
    # a chunk reads its context once where its queries' selections
    # together are more: every chunk but none here is smaller
    assert got["chunk_rows"] == 512 + 1024 + 1536 + 2048 + 2560 + 3000
    # a tiny topk: a chunk's queries select fewer rows than its context
    got = sparse.selected_work([(40, 0.0, [0.5])], 0.0, 1.0, 16, 1)
    assert got["chunk_rows"] == 16 + 16 + 8
    assert got["prefill_context"] == 40


def test_counts_agree_with_the_references_leaves():
    """853M parameters held: a layer's 96.9M (16 experts of 4.72M), 8 of
    them, and the embedding and the head's 18,992 rows."""
    ref = run.load_file("reference", "keye-vl-2-30b-a3b")
    shapes = ref.param_shapes(KEYE)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert round(total / 1e6) == 853
    matmul = sum(int(np.prod(s)) for k, s in shapes.items() if len(s) > 1)
    per_layer = sum(sparse.layer_params(KEYE).values()) \
        + 16 * sparse.pair_params(KEYE)
    assert round(per_layer / 1e5) == 969
    assert matmul == 8 * per_layer + 2 * 18992 * 2048
    assert total - matmul == 2048 + 8 * (2 * 2048 + 2 * 128)   # the gains
    assert set(KEYE["program"]["param_map"]) == set(shapes)


def test_the_configuration_keeps_every_published_width():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    for line in open(catalog) if os.path.exists(catalog) else []:
        row = json.loads(line)
        if row["name"] != "Keye-VL-2.0-30B-A3B":
            continue
        changed = {k for k, v in row["config"].items() if KEYE.get(k) != v}
        assert changed == set(KEYE["reduced"])
        assert KEYE["source"].startswith(row["source_url"])
    assert KEYE["reduced"] == ["num_hidden_layers", "num_experts",
                               "num_local_experts", "vocab_size"]
    assert (KEYE["hidden_size"], KEYE["num_attention_heads"],
            KEYE["num_key_value_heads"], KEYE["head_dim"]) == (
        2048, 32, 4, 128)
    assert (KEYE["moe_intermediate_size"], KEYE["num_experts_per_tok"],
            KEYE["intermediate_size"]) == (768, 8, 6144)
    assert KEYE["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert (KEYE["num_experts"], KEYE["num_local_experts"],
            KEYE["num_experts_published"], KEYE["experts_held"]) == (
        16, 16, 128, "0-15")
    assert KEYE["vocab_size"] * 8 == KEYE["vocab_size_published"] == 151936
    assert (KEYE["num_hidden_layers"],
            KEYE["num_hidden_layers_published"]) == (8, 48)
    manifest, entry = run.manifest_cell(CELL)
    config, = [c for c in manifest["configs"] if c["name"] == entry["config"]]
    assert config["reduced"] == KEYE["reduced"]
    assert entry["chips"] == 1 and entry["traffic"] == "serve-longdocs"
    cell = run.load_json("workloads", CELL + ".json")
    assert cell["engine"] == {"max_batch": 16, "max_seq_len": 33792,
                              "block_size": 64, "prefill_chunk": 512,
                              "max_queue": 100000}
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]


def _tiny(n_layers=2):
    import jax.numpy as jnp

    from perf import weights

    cfg = dict(KEYE, **rehearse._tiny(CELL)["configs/keye-vl-2-30b-a3b.json"],
               num_hidden_layers=n_layers)
    cfg["sa_config"] = dict(KEYE["sa_config"], indexer_head_dim=8,
                            indexer_num_heads=4, topk=12)
    ref = run.load_file("reference", "keye-vl-2-30b-a3b")
    w = weights.make(7, ref.param_shapes(cfg), std=0.2)
    rng = np.random.default_rng(3)
    rows = [(rng.integers(0, cfg["vocab_size"], 50).astype(np.int32), None)]
    return ref, cfg, w, rows, jnp


def test_the_controls_fail_and_the_references_own_tokens_pass():
    """The tokens the reference itself puts first lie 0 below its best;
    those of the reference in fp8, and those of a model that attends the
    latest 12 positions where the index picks 12, lie further: the check
    can see a lower precision and, at this size, a wrong selection."""
    ref, cfg, w, rows, jnp = _tiny()
    prompt = rows[0][0]
    lg = np.asarray(ref.logits(w, jnp.asarray(prompt)[None], cfg)[0])
    served = []
    for _ in range(8):      # greedy, by the reference itself
        seq = np.concatenate([prompt, np.asarray(served, np.int32)])
        lg = np.asarray(ref.logits(w, jnp.asarray(seq)[None], cfg)[0])
        served.append(int(lg[-1].argmax()))
    rows = [(prompt, np.asarray(served, np.int32))]
    own = ref.served_gaps(w, rows, cfg, 64)
    assert len(own[0]) == 8 and max(own[0]) < 1e-4
    gaps = {c: max(ref.served_gaps(w, rows, cfg, 64, control=c)[0])
            for c in ("bf16", "fp8", "recent")}
    assert gaps["fp8"] > gaps["bf16"] >= 0.0
    assert gaps["fp8"] > 1e-3 and gaps["recent"] > 1e-3
    with pytest.raises(ValueError, match="unknown precision"):
        ref.served_gaps(w, rows, cfg, 64, control="int4")


def _swapped_by_hand(ref, cfg, w, tok, t, layer):
    """The whole-sequence forward with position `t`'s router of `layer`
    taking its first expert left out for the last one taken: what
    `variants` computes from the sequence's keys, at that position."""
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    h = w["embed"][tok].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        lw = ref.layer_leaves(w, i)
        h = h + ref.attention(ref.rms_norm(h, lw["ln1_g"], eps, "fp32"), lw,
                              cfg, "fp32")[0]
        y = ref.rms_norm(h, lw["ln2_g"], eps, "fp32")
        swap = (jnp.arange(len(tok)) == t) & (i == layer)
        h = h + ref.routed_ffn(y, lw, cfg, "fp32", swap)[0]
    return ref.rms_norm(h, w["ln_g"].astype(jnp.float32), eps, "fp32")[t]


def test_a_swapped_path_is_the_forward_with_that_router_swapped():
    """`variants` on the sequence's own keys: without a swap it is the
    sequence's own state bit for bit, with one it is the whole forward in
    which that position's router of that layer took the other expert (a
    position within `topk` and some past it; every layer)."""
    import jax
    import jax.numpy as jnp

    ref, cfg, w, rows, _ = _tiny(3)
    tok = jnp.asarray(np.concatenate([rows[0][0], rows[0][0][:14]]))
    at = jnp.asarray([5, 11, 12, 40, 63])
    with jax.default_matmul_precision("highest"):
        own, (start, keys) = ref.hidden(w, tok, cfg, at=at)
        assert start.shape == (4, 5, cfg["hidden_size"]) and len(keys) == 3
        none = jnp.zeros((5, 3), bool)
        h, margin = ref.variants(w, start, keys, at, none, cfg)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(own[at]))
        assert margin.shape == (5, 3) and (np.asarray(margin) >= 0).all()
        moved = 0.0
        for layer in range(3):
            swaps = none.at[:, layer].set(True)
            h, _ = ref.variants(w, start, keys, at, swaps, cfg)
            for j, t in enumerate(np.asarray(at)):
                want = _swapped_by_hand(ref, cfg, w, tok, int(t), layer)
                np.testing.assert_allclose(np.asarray(h[j]),
                                           np.asarray(want), atol=1e-5)
            moved = max(moved, float(jnp.abs(h - own[at]).max()))
    assert moved > 1e-2     # some swap reaches an expert that is held here


def test_a_near_tie_taken_the_other_way_is_no_gap():
    """A token that the model puts first once ONE router's near tie falls
    the other way: its gap is what the reference's own path gives while
    `check.router_tie` is 0 or under that margin, and 0 once the margin
    lies under it.  The controls stay where they were: no path within a
    tie of their tokens' is the reference's."""
    import jax
    import jax.numpy as jnp

    ref, cfg, w, rows, _ = _tiny(3)
    tok = jnp.asarray(np.concatenate([rows[0][0], rows[0][0][:14]]))
    T, L = len(tok), 3
    at = jnp.repeat(jnp.arange(20, T - 1), L)
    swaps = jnp.tile(jnp.eye(L, dtype=bool), (T - 21, 1))
    with jax.default_matmul_precision("highest"):
        own, (start, keys) = ref.hidden(w, tok, cfg, at=at)
        h, margin = ref.variants(w, start, keys, at, swaps, cfg)
        e = ref.head_matrix(w)
        first, other = (np.asarray(jnp.argmax(ref.head(e, x), -1))
                        for x in (own[at], h))
    margin = np.asarray(margin)[np.arange(len(at)), np.tile(np.arange(L),
                                                          T - 21)]
    found = np.flatnonzero(first != other)
    assert len(found), "no swap moves the first token at this size"
    j = found[np.argmin(margin[found])]
    t, m = int(at[j]), float(margin[j])
    # the sequence up to t, then the token of the swapped path
    rows = [(np.asarray(tok[:t + 1]), np.asarray([other[j]], np.int32))]

    def gap(tie, control=None):
        c = dict(cfg, check={"router_tie": tie})
        return max(ref.served_gaps(w, rows, c, T, control=control)[0])

    strict = gap(0.0)
    assert strict > 1e-4
    assert gap(0.5 * m) == pytest.approx(strict, abs=1e-5)
    assert gap(1.5 * m) < 1e-4
    assert gap(1.5 * m, "recent") > 1e-3


def test_the_rehearsal_of_the_cell_is_correct():
    result = rehearse.run_tiny(CELL, seed=2**31 + 6, seconds=1.5, trace=1)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    # on the CPU no kernel runs, so the three rooflines say nothing; the
    # whole step's share, the selection's share and the nine readers that
    # know no model do
    assert {"step_mfu.serve.sparse", "sparse_selected_share"} \
        <= set(result["metrics"])
    assert not {n for n in result["metrics"] if "roofline" in n}
    assert {"batch_occupancy_pct", "sched_host_ms", "kv_pool_written_pct",
            "prefill_tokens_per_s"} <= set(result["metrics"])
    # 12 kept of contexts of 14-128: under 100, over 12 / 128
    assert 9.0 < result["metrics"]["sparse_selected_share"]["value"] < 100.0


def test_the_readers_leave_their_metric_out_where_nothing_is_to_read():
    """A program without the kernels or the ring's fields (the parent
    commit has none of them) gives None, not an error."""
    empty = {"ops": {"fusion.1": {"seconds": 1.0, "count": 1, "label": ""}},
             "modules": {}}
    old = (  # a ring whose records know nothing of an index
        [SimpleNamespace(t0=0.0, t1=0.5, causes=(0.0,), expert_pairs=3,
                         expert_tokens=9, expert_busiest=1)], True, ("x",))
    record = dict(trace=empty, trace_t0=0.0, trace_t1=1.0, t_open=0.0,
                  t_close=1.0, window_s=1.0, chunk=512, requests=[],
                  config=KEYE, peak=PEAK, ring=old,
                  work=dict(output_tokens=5, prompt_tokens=0, decode_tokens=5,
                            prefill_context=0, decode_context=50,
                            chunk_context=0, chunks=0))
    for name in NEW:
        assert run.load_file("metrics", name).read(record) is None, name
        if "roofline" in name:
            assert run.load_file("metrics", name).read({}) is None
    assert run.load_file("metrics", "step_mfu.serve.sparse").read({}) is None


def test_the_readers_on_counts_by_hand():
    """A slice that held one prompt of 3,000 (six chunks) and two decode
    tokens, its ring's counts and its kernels' seconds."""
    requests = [(3000, 0.0, [0.6, 0.7, 0.8])]
    ring = ([SimpleNamespace(t0=0.0, t1=0.65, causes=(0.0,),
                             index_positions_scored=8 * 3000 * 3001 // 2,
                             sparse_positions_attended=8 * 5_000_000,
                             expert_pairs=4000, expert_tokens=24000,
                             expert_busiest=50),
             SimpleNamespace(t0=0.65, t1=0.85, causes=(0.0,),
                             index_positions_scored=8 * 6003,
                             sparse_positions_attended=8 * 4096,
                             expert_pairs=16, expert_tokens=16,
                             expert_busiest=2)], True, ("x",))
    trace = {"ops": {"index_scores.3": {"seconds": 0.5, "count": 56},
                     "index_scores": {"seconds": 0.5, "count": 8},
                     "paged_attention_sparse.7": {"seconds": 2.0, "count": 64},
                     "moe_experts.1": {"seconds": 0.25, "count": 64},
                     "index_scores_not": {"seconds": 9.0, "count": 1}},
             "modules": {"jit_serving_step(1)": {"count": 2, "seconds": 1.0},
                         "jit_serving_prefill_chunk(2)": {"count": 6,
                                                          "seconds": 1.0}}}
    work = dict(output_tokens=3, prompt_tokens=3000, decode_tokens=2,
                prefill_context=3000 * 3001 // 2, decode_context=6003)
    record = dict(trace=trace, trace_t0=0.0, trace_t1=1.0, t_open=0.0,
                  t_close=1.0, window_s=1.0, chunk=512, requests=requests,
                  config=KEYE, peak=PEAK, ring=ring, work=work)

    def read(name):
        return run.load_file("metrics", name).read(record)

    scored = 8 * (3000 * 3001 // 2 + 6003)
    assert read("sparse_selected_share") == pytest.approx(
        100 * 8 * (5_000_000 + 4096) / scored)
    picked = sparse.selected_work(requests, 0.0, 1.0, 512, 2048)
    assert read("step_mfu.serve.sparse") == pytest.approx(
        100 * sparse.serve_flops(KEYE, work, picked, 4016) / 197e12)
    # the chunks read 512 + ... + 3,000 keys, the steps 3,001 + 3,002
    keys = 512 + 1024 + 1536 + 2048 + 2560 + 3000 + 6003
    floor = 8 * max(keys * 128 / 819e9,
                    2 * 16 * 64 * (3000 * 3001 // 2 + 6003) / 197e12)
    assert read("index_scores_roofline") == pytest.approx(100 * floor / 1.0)
    floor = sparse.sparse_floor_seconds(KEYE, picked, PEAK)
    assert read("sparse_attn_roofline") == pytest.approx(100 * floor / 2.0)
    counts = sparse.ring_counts(ring[0], 0.0, 1.0)
    assert (counts["pairs"], counts["tokens"]) == (4016, 24016)
    # 16 experts x 8 layers held; the busiest of an iteration's programs
    # over the mean an expert and layer: 50 / (4000 / 128), 2 / (16 / 128)
    assert read("expert_load_imbalance.sparse") == pytest.approx(
        (50 * 128 / 4000 + 2 * 128 / 16) / 2)
