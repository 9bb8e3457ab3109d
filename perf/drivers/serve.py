"""Driver of a closed-backlog serving cell: `ServingEngine.submit()` for
the whole list, the engine's own scheduler thread behind it.

Set-up builds the model at the configuration's sizes, gives it the seed's
weights, starts the engine with the cell's settings, compiles both programs
with two small requests, submits the backlog whole and opens the window
once `warm_finished` requests have finished (lanes then sit at staggered
phases).  The window then only watches: tokens are stamped by the engine
(`Request.t_tokens`), admissions by its request trace.  With a tracer the
traced slice (`trace_seconds`) follows the window's close and the
profiler's stop follows the last reading of the queue: window, trace,
stall.  A queue that is empty at the window's close or at the slice's end
ends the run with exit code 1 and no result.

After the window the engine is closed and freed, and the plain reference
runs once over a sample, drawn from the seed, of the requests the window
finished (the longest among them), prompt and served tokens together.
"""
from __future__ import annotations

import gc
import importlib
import json
import sys
import time

import numpy as np

from perf.work import ledger, served

now = time.monotonic      # the clock of Request.t_submit / t_tokens


def admitted_at(handle):
    """When the engine gave the request a lane (its trace's `admitted`
    event); None while it waits in the queue."""
    return next((e["t"] for e in handle.trace.snapshot()
                 if e["name"] == "admitted"), None)


class Driver:
    def __init__(self, *, cell, config, traffic, seed, reference, generate,
                 say):
        self.cell, self.cfg, self.mix = cell, config, traffic
        self.seed, self.ref, self.say = seed, reference, say
        self.generate = generate
        self.engine = self.net = None
        self.handles = []          # (request dict, Request)

    # ------------------------------------------------------------------ #
    def _build(self):
        import jax.numpy as jnp

        from incubator_mxnet_tpu.ndarray.ndarray import NDArray
        from incubator_mxnet_tpu.serving import ServingEngine
        from perf import weights

        cfg, prog = self.cfg, self.cfg["program"]
        mod, cls = prog["class"].split(":")
        kwargs = {k: cfg[v] for k, v in prog["kwargs"].items()}
        kwargs.update(prog.get("constants", {}))
        net = getattr(importlib.import_module(mod), cls)(**kwargs)
        net.initialize()
        net(NDArray(jnp.ones((1, 16), jnp.int32)))   # deferred shapes
        net.cast(cfg["dtype"])
        w = weights.make(self.seed, self.ref.param_shapes(cfg))
        weights.assign(weights.leaves(net, prog["param_map"], w), w)
        del w
        self.net = net
        self.engine = ServingEngine(net, **self.cell["engine"])

    def setup(self, seconds: float):
        self._build()
        cfg = self.cfg
        # both programs compile here, on two requests that are not traffic
        rng = np.random.default_rng(0)
        self.chunk = self.engine.stats()["prefill_chunk"]["chunk"]
        warm = [self.engine.submit(
            rng.integers(0, cfg["vocab_size"], n, dtype=np.int32), 4)
            for n in (self.chunk + 3, 5)]
        for h in warm:
            h.result(timeout=1100)
        for req in self.generate.requests(self.mix, self.seed,
                                          cfg["vocab_size"]):
            self.handles.append(
                (req, self.engine.submit(req["prompt"], req["max_new"])))
        want = self.cell["warm_finished"]
        while sum(h.finished for _, h in self.handles) < want:
            time.sleep(0.05)

    # ------------------------------------------------------------------ #
    def run(self, seconds: float, tracer) -> dict:
        s0 = self.engine.stats()
        t_open = now()
        time.sleep(seconds)
        t_close = now()
        s1 = last = self.engine.stats()
        self._backlog_held(s0, s1, last, t_close - t_open, "the window")
        record = {}
        if tracer is not None:
            # traced behind the window, over the same backlog: a traced
            # run's window is then the stretch an untraced run's is, and
            # stopping the profiler, which stalls this thread some 30 s
            # while the engine serves on, lies behind all that is measured
            record["trace_t0"] = now()
            tracer.start()
            time.sleep(self.cell["trace_seconds"])
            tracer.mark()
            record["trace_t1"] = now()
            last = self.engine.stats()
            # the ring's readers run after the stall: what they read is
            # copied now, while the ring still holds the window's opening
            record["ring"] = ledger.read_ring(t_open)
            try:
                self._backlog_held(s0, s1, last, record["trace_t1"] - t_open,
                                   "the traced slice behind the window")
            finally:
                tracer.stop()
        # the prompts being prefilled at the last reading (and so those at
        # the window's close) are placed by their first tokens' stamps:
        # wait for those (a second or two)
        due = last["admitted"] + last["prefill_chunk"]["jobs"]
        t_give_up = now() + 60.0
        while now() < t_give_up and self.engine.stats()["admitted"] < due:
            time.sleep(0.05)
        record.update(self._measure(t_open, t_close, s0, s1, last))
        return record

    def _backlog_held(self, s0, s1, last, elapsed, where):
        """Ends the run, exit code 1 and no result, where the queue is
        empty at the close of `where`: lanes then run empty, and a window
        or a trace of an emptying engine is no measurement."""
        if last["queue_depth"]:
            return
        self.release()
        used = s0["queue_depth"] - last["queue_depth"]
        sys.exit(
            f"the backlog ran out inside {where}: mix "
            f"{self.mix.get('name', '?')!r}, count {self.mix['count']}, "
            f"queue {s0['queue_depth']} at the window's open, "
            f"{s1['queue_depth']} at its close, {last['queue_depth']} at "
            f"the last reading, {used / elapsed:.1f} requests consumed a "
            "second. The mix's `count` is too small for this program: "
            "perf/README.md wants four times what a traced run consumes")

    def _measure(self, t_open, t_close, s0, s1, last) -> dict:
        window = t_close - t_open
        rows = [(len(r["prompt"]), admitted_at(h), list(h.t_tokens), h)
                for r, h in self.handles]
        rows = [x for x in rows if x[1] is not None]
        requests = [x[:3] for x in rows]
        work = served.count_work(requests, t_open, t_close, self.chunk)
        gaps = work.pop("gaps")
        counted = [(r, h) for r, h in self.handles
                   if h.finished and h.t_done is not None
                   and t_open <= h.t_done < t_close]
        self.finished = [(r, h) for r, h in counted if h.status == "done"]
        # all the work of the window: the prompt tokens prefilled in it,
        # chunk by chunk, and the output tokens stamped in it
        e2e = {"serve_tokens_per_s":
               (work["output_tokens"] + work["prompt_tokens"]) / window}
        if gaps:
            e2e["gap_p95_ms"] = 1e3 * float(np.percentile(gaps, 95))
        left = last["queue_depth"] / self.mix["count"]
        bs = self.cell["engine"]["block_size"]
        # positions written in the pool when the window closed
        live = sum(P + sum(t < t_close for t in stamps)
                   for P, t_admit, stamps, h in rows
                   if t_admit < t_close and not (
                       h.t_done is not None and h.t_done < t_close))
        self.say(json.dumps({
            "window_s": window, "output_tokens": work["output_tokens"],
            "prompt_tokens": work["prompt_tokens"],
            "chunks": work["chunks"], "gap_samples": len(gaps),
            "requests_finished": len(counted),
            "requests_ok": len(self.finished),
            "steps": s1["steps"] - s0["steps"],
            "queue_depth_open_close": [s0["queue_depth"], s1["queue_depth"]],
            # at the last check (the end of the traced slice, else the
            # window's close), over the mix's count
            "queue_depth_last": last["queue_depth"],
            "backlog_left_share": left,
            "pool_reserved_share_open_close": [
                1 - s["blocks_free"] / s["blocks_total"] for s in (s0, s1)],
            "pool_live_share_close": live / (bs * s1["blocks_total"]),
            "shed": s1["shed"], "evicted": s1["evicted"],
            "gap_ms_p50_p95_p99_max": [1e3 * float(np.percentile(gaps, q))
                                       for q in (50, 95, 99, 100)]
            if gaps else None,
            # a run that stalls shows here first (PERF.md section 7)
            "gaps_over_twice_the_median": int(np.sum(
                np.asarray(gaps) > 2 * np.median(gaps))) if gaps else 0}))
        return dict(
            attempted=len(counted), failed=len(counted) - len(self.finished),
            window_s=window, t_open=t_open, t_close=t_close, work=work,
            backlog_left_share=left,
            steps=s1["steps"] - s0["steps"],
            max_batch=self.cell["engine"]["max_batch"], chunk=self.chunk,
            config=self.cfg, requests=requests, end_to_end=e2e)

    # ------------------------------------------------------------------ #
    def release(self):
        try:
            self.engine.close()
        finally:
            self.engine = self.net = None
            gc.collect()

    def _sample(self) -> list:
        """`check_requests` of the requests the window finished, drawn
        from the seed, the longest among them."""
        done = [(r, h) for r, h in self.finished
                if len(h.tokens) == r["max_new"]]
        if not done:
            return []
        done.sort(key=lambda rh: -(len(rh[0]["prompt"]) + len(rh[1].tokens)))
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 11])
        k = min(self.cell["check_requests"], len(done)) - 1
        pick = [0] + sorted(1 + rng.choice(len(done) - 1, k, replace=False)
                            ) if k > 0 else [0]
        return [(done[i][0]["prompt"], np.asarray(done[i][1].tokens,
                                                  np.int32)) for i in pick]

    def check(self, record) -> list:
        from perf import compare, weights

        self.rows = self._sample()
        self.w = weights.make(self.seed, self.ref.param_shapes(self.cfg))
        pad = self.cell["engine"]["max_seq_len"]
        per_row = self.ref.served_gaps(self.w, self.rows, self.cfg, pad)
        gaps = [g for row in per_row for g in row]
        return compare.served(gaps, self.cell["limits"], {
            "requests_compared": len(self.rows),
            "longest": max((len(p) + len(t) for p, t in self.rows),
                           default=0),
            "row_widest": [max(r) if r else None for r in per_row]},
            self.say)

    def control(self, record) -> dict:
        """The reference computed in the precision below the stated one,
        put in the program's place: at every position of the same prompts
        and tokens, the gap of the token IT puts first."""
        from perf import compare

        pad = self.cell["engine"]["max_seq_len"]
        out = {}
        for prec in ("fp8", "bf16"):
            per_row = self.ref.served_gaps(self.w, self.rows, self.cfg, pad,
                                           control=prec)
            out["control_" + prec] = compare.served(
                [g for row in per_row for g in row], self.cell["limits"],
                {}, lambda *a: None)
        return out
