"""`correct` has to be able to come out false.  Each test skips the
harness's look for a chip, drives the rest of a run at a tiny size on the
CPU with the timed path broken underneath, and reads `correct` from the
run's own last line.  The limit is the cell's own (set on the chip at the
cell's size, PERF.md section 2).  A sound tiny run stays inside it.

The control (the reference in the precision below the stated one) is read
on the chip by `perf/calibrate.py`; here it is held to the same limit at
the tiny size.
"""
import numpy as np
import pytest

from perf import compare, run
from perf.tests import rehearse

SERVE = "gpt2-medium.serve-batch"


def _compared(result):
    return {c["name"]: (c["value"], c["limit"]) for c in result["compared"]}


def test_sound_serving_run_is_correct():
    result = rehearse.run_tiny(SERVE, seed=2**31 + 6, seconds=1.5)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "gap_p95_ms",
                                      "setup_s"}


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from incubator_mxnet_tpu.serving import engine

    real = engine.Request._deliver

    def altered(self, tok, now):
        # one token in seven comes out as its neighbour
        if len(self.tokens) % 7 == 3:
            tok = (tok + 1) % 640
        return real(self, tok, now)

    monkeypatch.setattr(engine.Request, "_deliver", altered)
    result = rehearse.run_tiny(SERVE, seed=13, seconds=1.5)
    assert result["correct"] is False
    value, limit = _compared(result)["served_logit_widest_gap"]
    assert value > limit


# what a test run can hold of the cell: an eighth of the vocabulary, half
# the width, a third of the depth.  The control's gap grows with width and
# depth (0.68-0.78 at the cell's own size on the chip; PERF.md section 2)
SMALL = dict(n_embd=512, n_inner=2048, n_layer=8, n_head=8, vocab_size=8192,
             n_positions=128)


@pytest.mark.parametrize("seed", [3, 4, 2**31 + 5])
def test_the_control_fails_and_the_stated_precision_passes(seed):
    """The fp8 reference in the program's place, held to the cell's limit:
    at the positions of prompts and tokens, the token IT puts first lies
    further below the float32 reference's best than the limit; the token
    the bf16 reference (the precision the configuration states) puts
    first does not."""
    from perf import weights

    ref = run.load_file("reference", "gpt2-medium")
    cfg = dict(run.load_json("configs", "gpt2-medium.json"), **SMALL)
    limits = run.load_json("workloads", SERVE + ".json")["limits"]
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
