"""Open-loop serving load harness — continuous batching under overload.

Drives the paged-KV ServingEngine (incubator_mxnet_tpu/serving/) with
Poisson arrivals at a configurable offered load, optionally injecting
faults (a slowed decode step, mid-flight client cancellations), and
reports the latency/goodput envelope:

    python benchmark/serving_bench.py [--rate HZ] [--requests N]
        [--max-batch B] [--max-queue Q] [--prompt-len P] [--new-tokens T]
        [--slow-step-ms MS] [--cancel-frac F] [--kv-dtype model|int8]
        [--speculate K] [--draft int8|tiny]
        [--shared-prefix-frac F] [--prefill-chunk N]
        [--long-prompt-every K]
        [--sweep-prompt-lens P1,P2,...] [--seed S] [--out FILE]
        [--profile] [--profile-out TRACE.json]

Open loop: arrival gaps are pre-sampled exponentials and submit() never
blocks on the engine — requests the bounded queue cannot hold are shed,
exactly as a real frontend would see.  Per-request timestamps come from
the engine itself (Request.t_submit / t_first / t_done), so TTFT
includes queueing delay and TPOT is pure decode cadence.

Emits ONE BENCH-style JSON row (the repo convention, see bench.py):
{"metric", "value", "unit", "detail"} where value is
GOODPUT UNDER SLO — decoded tok/s of requests that completed AND met
both latency targets (``--ttft-slo-ms``, ``--tpot-slo-ms``; shed,
evicted and SLO-violating work all count as zero, the number a
capacity planner actually provisions against) — and detail carries raw
goodput, offered load, shed fraction and TTFT/TPOT p50/p95/p99.

``--kv-dtype int8`` runs the same harness against an int8-KV-pool
engine (ISSUE 15): pages quantize at write time, the attention
dequantizes in-kernel, and ``detail.kv_bytes_per_token`` records the
capacity win.  ``--sweep-prompt-lens 24,96,192`` appends compact
secondary rows under ``detail.prompt_sweep`` — the longer-prompt
regime where dense-gather attention traffic grows with ``max_seq_len``
while the paged kernel's page walk stays length-bounded.

``--speculate K`` (ISSUE 19) turns on draft/verify speculative
decoding: a cheap draft proposes K tokens per lane per scheduler
iteration and the target verifies all of them in ONE batched forward —
one target weight stream amortized over up to K+1 tokens per lane.
``--draft int8`` (default) self-drafts with the target's own
int8-quantized twin (high acceptance, no second model);
``--draft tiny`` uses a fresh small TransformerLM (cheaper draft,
lower acceptance).  Greedy output is bit-identical to the
non-speculative engine either way; ``detail.speculate`` reports the
measured acceptance rate and tokens-per-lane-step.

``--shared-prefix-frac F`` (ISSUE 20) makes every short prompt share
its first ``int(F * prompt_len)`` tokens — the system-prompt traffic
shape the copy-on-write prefix cache serves without re-prefilling:
after the first admission registers the shared blocks, later requests
bind them and chunk-prefill only their private tail.
``detail.prefix_cache`` carries the engine's hit/miss/cached-token
counters, and a cold CONTROL pass at the same config (prefix sharing
off) lands under ``detail.prefix_cache_control`` with the measured
TTFT p50 reduction.  ``--prefill-chunk N`` sets the engine's fixed chunk width
(default: the engine's own default).  ``--long-prompt-every K`` runs a
SECOND measured pass where every K-th request carries a cold 2x-length
prompt — the head-of-line-blocking regime chunked prefill exists for —
and reports tpot p99 over the SHORT requests (the victims of a
monolithic prefill) next to the steady-state p99 under
``detail.long_prompt_arrival``.

``--profile`` (ISSUE 17) enables telemetry for the measured run and
carries the stall-attribution table + recent hiccup records under
``detail.profile``, so a BENCH row explains WHERE the step time went
alongside how much goodput it bought; ``--profile-out FILE`` also
writes the merged chrome-trace JSON (request/scheduler/program lanes)
for chrome://tracing / Perfetto.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax
import jax.numpy as jnp
import numpy as np

# bench model: big enough that a decode step does real work, small
# enough to warm up in seconds on any host
V, C, DFF, L, H = 1024, 128, 512, 2, 4


def _pct(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(round(q / 100.0 * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rate", type=float, default=20.0,
                    help="offered load, requests/s (Poisson)")
    ap.add_argument("--requests", type=int, default=80)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--slow-step-ms", type=float, default=0.0,
                    help="fault injection: model a slow device costing "
                         "this long per batched decode step, and "
                         "proportionally per prefill chunk "
                         "(MS * chunk_width / max_batch — same "
                         "per-token device cost)")
    ap.add_argument("--cancel-frac", type=float, default=0.0,
                    help="fault injection: cancel this fraction of "
                         "requests ~one step after submission")
    ap.add_argument("--ttft-slo-ms", type=float, default=2000.0,
                    help="TTFT target a request must meet to count "
                         "toward goodput-under-SLO")
    ap.add_argument("--tpot-slo-ms", type=float, default=500.0,
                    help="TPOT target a request must meet to count "
                         "toward goodput-under-SLO")
    ap.add_argument("--kv-dtype", choices=("model", "int8"),
                    default="model",
                    help="KV pool dtype: 'int8' quantizes pages at "
                         "write time (fp32 per-vector scales ride "
                         "alongside, dequant happens in the attention)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per lane "
                         "per step, verify them in one batched target "
                         "forward (0 = off)")
    ap.add_argument("--draft", choices=("int8", "tiny"), default="int8",
                    help="draft model for --speculate: 'int8' "
                         "self-drafts with the target's quantized twin, "
                         "'tiny' uses a fresh small TransformerLM")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    metavar="F",
                    help="short prompts share their first int(F * "
                         "prompt_len) tokens; the prefix cache serves "
                         "the shared blocks after the first admission")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    metavar="N",
                    help="fixed prefill chunk width (tokens per chunk "
                         "program call); default: engine default")
    ap.add_argument("--long-prompt-every", type=int, default=0,
                    metavar="K",
                    help="also run a long-prompt-arrival pass: every "
                         "K-th request carries a cold 2x-length prompt; "
                         "reports short-request tpot p99 under "
                         "detail.long_prompt_arrival (0 = off)")
    ap.add_argument("--sweep-prompt-lens",
                    help="comma-separated extra prompt lengths; each "
                         "runs the same open loop and lands a compact "
                         "row under detail.prompt_sweep")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", help="also write the JSON row here")
    ap.add_argument("--profile", action="store_true",
                    help="enable telemetry for the measured run and "
                         "carry the stall-attribution table + recent "
                         "hiccups under detail.profile")
    ap.add_argument("--profile-out",
                    help="with --profile: write the merged chrome-trace "
                         "JSON (request/scheduler/program lanes) here")
    args = ap.parse_args()
    if args.profile_out and not args.profile:
        ap.error("--profile-out requires --profile")

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    sweep_lens = [int(s) for s in args.sweep_prompt_lens.split(",")] \
        if args.sweep_prompt_lens else []

    if args.profile:
        # the stall ledger runs regardless; telemetry must be ON for
        # its histograms, trace lanes and program timings to record
        from incubator_mxnet_tpu import telemetry

        telemetry.enable()

    mx.random.seed(args.seed)
    max_prompt = max([args.prompt_len] + sweep_lens
                     + ([2 * args.prompt_len] if args.long_prompt_every
                        else []))
    net = TransformerLM(vocab=V, units=C, hidden_size=DFF, num_layers=L,
                        num_heads=H,
                        max_len=max_prompt + args.new_tokens + 40,
                        dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 4), jnp.int32)))
    net.cast("bfloat16")

    args.spec_kw = {}
    if args.speculate > 0:
        args.spec_kw["speculate_k"] = args.speculate
        if args.draft == "int8":
            # the target's int8 twin IS the draft; the serving target
            # itself stays bf16 (quantized=False)
            net.quantize_for_decode(act_quant="none")
            args.spec_kw["quantized"] = False
        else:
            mx.random.seed(args.seed + 1)
            draft = TransformerLM(vocab=V, units=C // 2,
                                  hidden_size=DFF // 2, num_layers=1,
                                  num_heads=H // 2,
                                  max_len=max_prompt + args.new_tokens + 40,
                                  dropout=0.0)
            draft.initialize()
            draft(NDArray(jnp.ones((1, 4), jnp.int32)))
            draft.cast("bfloat16")
            args.spec_kw["draft_net"] = draft

    run = _run_once(args, net, args.prompt_len)
    row = _render_row(args, run)
    if sweep_lens:
        row["detail"]["prompt_sweep"] = [
            _sweep_summary(args, net, plen) for plen in sweep_lens]
    if args.shared_prefix_frac > 0:
        # cold control at the SAME config: the measured win of serving
        # the shared prefix from cache instead of re-prefilling it
        ctrl = argparse.Namespace(**vars(args))
        ctrl.shared_prefix_frac = 0.0
        creqs, _, _, _ = _run_once(ctrl, net, args.prompt_len)
        cold = _ttft_p50_ms(creqs)
        warm = row["detail"]["ttft_ms"]["p50"]
        row["detail"]["prefix_cache_control"] = {
            "ttft_p50_ms_cold": cold,
            "ttft_p50_ms_shared": warm,
            "ttft_p50_reduction": (None if not warm or not cold
                                   else round(cold / warm, 2))}
    if args.long_prompt_every:
        row["detail"]["long_prompt_arrival"] = _long_prompt_summary(
            args, net)
    line = json.dumps(row)
    print(line)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(line + "\n")
    if args.profile_out:
        with open(args.profile_out, "w", encoding="utf-8") as fh:
            json.dump(run[3]["trace"], fh)


def _run_once(args, net, prompt_len, long_every=0, long_msl=False):
    """One open-loop measured run; returns the raw observations."""
    from incubator_mxnet_tpu.serving import ServingEngine

    long_len = 2 * prompt_len
    msl = (long_len if long_every or long_msl else prompt_len) \
        + args.new_tokens + 8
    eng = ServingEngine(net, max_batch=args.max_batch, block_size=16,
                        max_seq_len=msl, max_queue=args.max_queue,
                        kv_dtype="int8" if args.kv_dtype == "int8" else None,
                        prefill_chunk=args.prefill_chunk,
                        slo_ttft=args.ttft_slo_ms / 1e3,
                        slo_tpot=args.tpot_slo_ms / 1e3,
                        **getattr(args, "spec_kw", {}))

    rng = np.random.RandomState(args.seed)
    share = int(round(args.shared_prefix_frac * prompt_len))
    shared = rng.randint(0, V, size=share).astype(np.int32)
    # long prompts are COLD (no shared prefix): the head-of-line
    # stressor is a full-length chunked prefill, not a cache hit
    long_idx = {i for i in range(args.requests)
                if long_every and i and i % long_every == 0}
    prompts = []
    for i in range(args.requests):
        if i in long_idx:
            prompts.append(rng.randint(0, V, size=long_len)
                           .astype(np.int32))
        else:
            tail = rng.randint(0, V, size=prompt_len - share) \
                      .astype(np.int32)
            prompts.append(np.concatenate([shared, tail]))
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    cancel = rng.random_sample(args.requests) < args.cancel_frac

    # warmup: compile the chunk + step programs OUTSIDE the timed run
    # (with a shared prefix this also registers it — the steady state a
    # prefix-cache deployment actually serves from)
    eng.submit(prompts[0], args.new_tokens).result(timeout=120)
    assert eng.drain(timeout=60)
    if args.slow_step_ms > 0:
        # consistent synthetic device: a decode step carries up to
        # max_batch tokens for slow_step_ms, so an N-token prefill
        # chunk on the same device costs slow_step_ms * N / max_batch
        step_s = args.slow_step_ms / 1e3
        chunk_s = step_s * (eng.stats()["prefill_chunk"]["chunk"]
                            / args.max_batch)
        eng.set_fault_hook(
            lambda ph: time.sleep(step_s if ph == "step" else chunk_s)
            if ph in ("step", "prefill") else None)

    reqs = []
    t0 = time.monotonic()
    for i in range(args.requests):
        time.sleep(gaps[i])
        r = eng.submit(prompts[i], args.new_tokens, seed=i)
        reqs.append(r)
        if cancel[i]:
            r.cancel()
    assert eng.drain(timeout=600), "engine failed to drain"
    wall = time.monotonic() - t0
    stats = eng.stats()
    info = {"kv_bytes_per_token": eng.kv_bytes_per_token,
            "attn_impl": eng.attn_impl,
            "prefix_cache": stats["prefix_cache"],
            "prefill_chunk": stats["prefill_chunk"]["chunk"],
            "long_idx": long_idx}
    if args.speculate > 0:
        spec = stats["speculate"]
        info["speculate"] = {
            "k": spec["k"],
            "draft": spec["draft"],
            "accept_rate": round(spec["accept_rate"], 4),
            # per lane-iteration: 1 committed token + k*accept_rate
            # accepted drafts (the amortization factor over one target
            # weight stream)
            "tokens_per_lane_step": round(
                1.0 + spec["k"] * spec["accept_rate"], 2),
            "proposed": spec["proposed"],
            "accepted": spec["accepted"],
        }
    if args.profile:
        prof = eng.profiler
        info["profile"] = {
            "stall_attribution": eng.stall_table(),
            "hiccups": prof.recent_stalls(8),
            "hiccups_total": prof.hiccups_total,
            "invariant_violations": prof.invariant_violations,
        }
        if args.profile_out:
            # capture BEFORE close(): the engine's scheduler lane
            # unregisters from the merged timeline at close
            info["trace"] = eng.capture_profile(0)
    eng.close()
    return reqs, stats, wall, info


def _sweep_summary(args, net, prompt_len):
    """Compact secondary row for one sweep length."""
    reqs, stats, wall, info = _run_once(args, net, prompt_len)
    done = [r for r in reqs if r.status == "done"]
    slo_ok = [r for r in done
              if (r.ttft is None or r.ttft <= args.ttft_slo_ms / 1e3)
              and (r.tpot is None or r.tpot <= args.tpot_slo_ms / 1e3)]
    tpot = sorted((r.t_done - r.t_first) / (len(r.tokens) - 1)
                  for r in done if len(r.tokens) > 1)
    p50 = _pct(tpot, 50)
    return {"prompt_len": prompt_len,
            "goodput_under_slo": round(
                sum(len(r.tokens) for r in slo_ok) / wall, 1),
            "served_under_slo": len(slo_ok),
            "tpot_p50_ms": None if p50 is None else round(p50 * 1e3, 2),
            "wall_s": round(wall, 2)}


def _ttft_p50_ms(reqs):
    tt = sorted(r.t_first - r.t_submit for r in reqs
                if r.status == "done" and r.t_first is not None)
    p = _pct(tt, 50)
    return None if p is None else round(p * 1e3, 2)


def _short_tpot_p99_ms(reqs, long_idx):
    """p99 over INDIVIDUAL inter-token gaps of the short requests (one
    sample per decoded token, not per-request means): a monolithic
    prefill's stall cannot hide inside a request's average."""
    gaps = []
    for i, r in enumerate(reqs):
        if i in long_idx or r.status != "done":
            continue
        gaps.extend(b - a for a, b in zip(r.t_tokens, r.t_tokens[1:]))
    gaps.sort()
    p99 = _pct(gaps, 99)
    return None if p99 is None else round(p99 * 1e3, 2)


def _long_prompt_summary(args, net):
    """Two passes on the IDENTICAL engine config (same max_seq_len, so
    same pool and program shapes): a steady all-short baseline, then
    one where a cold 2x-length prompt arrives every K-th request.  tpot
    p99 is computed over the SHORT requests only — the victims a
    monolithic prefill would stall for the whole long prompt; with
    chunked prefill their decode cadence should barely move."""
    sreqs, _, _, _ = _run_once(args, net, args.prompt_len, long_msl=True)
    steady = _short_tpot_p99_ms(sreqs, set())
    reqs, stats, wall, info = _run_once(args, net, args.prompt_len,
                                        long_every=args.long_prompt_every)
    longs = info["long_idx"]
    p99_ms = _short_tpot_p99_ms(reqs, longs)
    return {"every": args.long_prompt_every,
            "long_prompt_len": 2 * args.prompt_len,
            "long_served": sum(1 for i, r in enumerate(reqs)
                               if i in longs and r.status == "done"),
            "short_served": sum(1 for i, r in enumerate(reqs)
                                if i not in longs and r.status == "done"),
            "short_tpot_p99_ms": p99_ms,
            "steady_tpot_p99_ms": steady,
            "ratio_vs_steady": (None if not p99_ms or not steady
                                else round(p99_ms / steady, 2)),
            "wall_s": round(wall, 2)}


def _render_row(args, run):
    reqs, stats, wall, info = run
    done = [r for r in reqs if r.status == "done"]
    shed = sum(stats["shed"].values())
    evicted = sum(stats["evicted"].values())
    cancelled = sum(1 for r in reqs if r.status == "cancelled")
    ttft = sorted(r.t_first - r.t_submit for r in done
                  if r.t_first is not None)
    tpot = sorted((r.t_done - r.t_first) / (len(r.tokens) - 1)
                  for r in done if len(r.tokens) > 1)
    good_tokens = sum(len(r.tokens) for r in done)
    goodput = good_tokens / wall
    # goodput UNDER SLO: only requests meeting both latency targets
    # (the engine derives r.ttft / r.tpot at finish time)
    slo_ok = [r for r in done
              if (r.ttft is None or r.ttft <= args.ttft_slo_ms / 1e3)
              and (r.tpot is None or r.tpot <= args.tpot_slo_ms / 1e3)]
    slo_tokens = sum(len(r.tokens) for r in slo_ok)

    row = {
        "metric": "serving_goodput_under_slo",
        "value": round(slo_tokens / wall, 1),
        "unit": "tok/s",
        "detail": {
            "offered_load_hz": args.rate,
            "requests": args.requests,
            "served": len(done),
            "served_under_slo": len(slo_ok),
            "goodput_raw": round(goodput, 1),
            "ttft_slo_ms": args.ttft_slo_ms,
            "tpot_slo_ms": args.tpot_slo_ms,
            "shed": shed,
            "shed_fraction": round(shed / args.requests, 4),
            "evicted": evicted,
            "cancelled": cancelled,
            "ttft_ms": {"p50": _pct(ttft, 50), "p95": _pct(ttft, 95),
                        "p99": _pct(ttft, 99)},
            "tpot_ms": {"p50": _pct(tpot, 50), "p95": _pct(tpot, 95),
                        "p99": _pct(tpot, 99)},
            "decode_steps": stats["steps"],
            "wall_s": round(wall, 2),
            "max_batch": args.max_batch,
            "max_queue": args.max_queue,
            "prompt_len": args.prompt_len,
            "new_tokens": args.new_tokens,
            "slow_step_ms": args.slow_step_ms,
            "cancel_frac": args.cancel_frac,
            "shared_prefix_frac": args.shared_prefix_frac,
            "prefill_chunk": info["prefill_chunk"],
            "prefix_cache": info["prefix_cache"],
            "kv_dtype": args.kv_dtype,
            "attn_impl": info["attn_impl"],
            "kv_bytes_per_token": info["kv_bytes_per_token"],
            "model": f"TransformerLM {L}L/{C}D V={V} bf16",
            "device": jax.devices()[0].device_kind,
        },
    }
    for d in (row["detail"]["ttft_ms"], row["detail"]["tpot_ms"]):
        for k, v in d.items():
            d[k] = None if v is None else round(v * 1e3, 2)
    if "speculate" in info:
        row["detail"]["speculate"] = info["speculate"]
    if "profile" in info:
        row["detail"]["profile"] = info["profile"]
    return row


if __name__ == "__main__":
    main()
