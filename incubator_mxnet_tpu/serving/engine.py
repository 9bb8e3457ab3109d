"""Continuous-batching serving engine with overload safety.

`ServingEngine` runs an iteration-level (Orca-style) scheduler on a
background thread: between decode steps it retires finished sequences,
evicts timed-out/cancelled ones, admits queued requests, runs ONE
fixed-width prefill chunk for the oldest admitted-but-unprefilled
request, then executes ONE batched decode step for every live lane —
so a long prompt costs each resident sequence at most one chunk of
extra latency per token, never its whole prefill (ISSUE 20).  The
decode loop runs one step AHEAD of its reads: a step is handed to the
device before the tokens of the step before it are read, so the host's
bookkeeping and dispatch overlap the device's work (docs/serving.md,
"One step ahead"; an EOS, a cancel and a freed lane are learnt one
step late, each exactly).  The KV
cache is a paged pool (`kv_pool`, `programs`): admission and eviction
move *block table entries*, never array shapes, so after warmup
nothing recompiles — ci/serving_smoke.py pins this with a zero-budget
RetraceGuard.

Admission is copy-on-write prefix-cached (ISSUE 20): the BlockPool
content-addresses full KV blocks by prefix-token hash, so a request
whose prompt shares a block-aligned prefix with earlier traffic binds
those blocks read-only (refcounted — `free` is a decref) and prefills
only its uncached tail.  Cache-hit greedy output is bit-identical to
a cold prefill (docs/serving.md §"Prefix caching"), and the draft
pool shares the same tables and block ids, so speculation composes.

The robustness envelope (the reason this engine exists — an engine
that stalls or corrupts neighbours under overload is worse than none):

* **Bounded admission queue** — `submit(block=False)` (default) SHEDS
  when the queue is full (`RequestShed`, counted in
  ``serving_shed_total{reason="queue_full"}``, never an unbounded
  buffer); `block=True` waits with backpressure, observing close().
* **SLO-aware shedding** — with a ``ttft_budget``, a request whose
  estimated TTFT (queue wait so far + EWMA prefill time) already
  exceeds the budget is shed at admission instead of admitted late.
* **Deadlines** — a request past its deadline is shed while queued and
  EVICTED mid-batch while running; eviction frees its blocks and
  leaves every co-batched sequence bit-identical to an unperturbed run
  (docs/serving.md §"Why eviction is exact" — lanes are independent
  and masked scratch reads contribute exactly 0.0).
* **Cancellation** — `Request.cancel()` is non-blocking and safe from
  any thread; `Request.stream()` cancels in a ``finally`` so a caller
  abandoning the generator mid-stream releases the KV blocks (the
  r12 leak fix; regression-tested).
* **Clean shutdown** — `close()` stops and JOINS the scheduler thread
  (tpulint TPU012); scheduler errors are parked under a lock and
  re-raised on the caller (TPU011, the checkpoint-worker idiom), and a
  failed engine refuses new work instead of hanging it.

The observability plane (ISSUE 13) rides every state transition above:
each request carries a `telemetry.requestlog.RequestTrace` span
timeline (submit → queued → admitted → prefill → per-N-decode-step
marks → terminal, block/occupancy annotations included; requests shed
BEFORE admission get a complete submit → shed trace too), completed
traces land in the process-wide bounded ring `/requestz` serves; an
`SloTracker` feeds ``serving_slo_fraction{window=}`` /
``serving_slo_burn_rate{window=}`` from TTFT/TPOT targets; `health()`
reports scheduler liveness + queue/KV headroom + SLO burn with
healthy/degraded/unhealthy semantics; the env-gated
(``MXTPU_TELEMETRY_PORT``) `telemetry.http.TelemetryServer` is started
at construction and JOINED by `close()`; and a flight-recorder section
hook puts the in-flight table + recent traces into SIGTERM bundles.

Thread-safety: ONE lock (`self._lock`, shared by the `self._work`
condition and every request's condition) guards the queue, slots,
stats and pool accounting.  Everything on the device — programs, K/V
pools, recurrent state, weights — belongs to `programs.PagedPrograms`
(this module imports no JAX); the scheduler thread is the only caller
of its program calls, so device calls run lock-free; only
bookkeeping holds the lock.  That includes prefill (tpulint TPU015):
admission claims the lane + blocks under the lock (binding any
cache-hit prefix blocks), each chunk is stage (under the lock) →
device call (unlocked) → commit (re-lock, slot-identity check), and
the final chunk's commit delivers the first token — mirroring the
decode step's snapshot (`_loop`) / hand-over (`_decode_step`) / commit
(`_land_step`) shape.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Optional

import numpy as np

from .. import telemetry
from .kv_pool import SCRATCH_BLOCK, BlockPool
from .programs import PagedPrograms, wide_counts

__all__ = ["ServingError", "RequestShed", "RequestTimedOut",
           "RequestCancelled", "RequestFailed", "Request", "ServingEngine",
           "default_engine"]

# defaults of the constructor options poll_interval, max_queue,
# prefill_chunk (tokens: the scheduler's prefill budget per iteration —
# one chunk of at most this many prompt positions runs between
# consecutive decode steps) and slo_ttft (seconds, when ttft_budget is
# not given either)
_POLL_S = 0.002
_MAX_QUEUE = 16
_PREFILL_CHUNK = 32
_SLO_TTFT_S = 1.0
# one trace mark per N decode steps per request (admission/terminal
# events always record)
_TRACE_EVERY = 8

# engine names for the HTTP/flight-recorder provider registries
_engine_ids = itertools.count(1)

# terminal request statuses (everything else is live)
_TERMINAL = ("done", "shed", "evicted", "cancelled", "failed")


class ServingError(RuntimeError):
    """Base class for per-request serving failures."""


class RequestShed(ServingError):
    """Rejected by admission control (bounded queue / SLO estimate /
    queued-past-deadline); carries ``.reason``."""

    def __init__(self, reason: str):
        super().__init__(f"request shed ({reason})")
        self.reason = reason


class RequestTimedOut(ServingError):
    """Evicted mid-batch: the per-request deadline passed."""


class RequestCancelled(ServingError):
    """Cancelled by the caller (or by engine shutdown)."""


class RequestFailed(ServingError):
    """The scheduler hit an internal error; the cause is chained."""


class Request:
    """A submitted generation request — a future over its token stream.

    ``tokens`` grows as the engine emits (generated tokens only, prompt
    excluded); `result()` blocks for completion, `stream()` iterates
    tokens as they land and CANCELS on early exit.  Timing fields
    (``t_submit``/``t_first``/``t_done``, ``time.monotonic`` seconds)
    feed the load harness's TTFT/TPOT percentiles and are recorded for
    EVERY terminal status — a request shed before admission still gets
    ``t_done``, a ``finish_reason`` and a complete ``trace``, so
    rejected traffic is explainable, not just served traffic.

    ``trace`` is the request's `telemetry.requestlog.RequestTrace`
    lifecycle timeline; it is pushed into the process-wide recent-trace
    ring (``/requestz``) when the request reaches a terminal status.
    """

    def __init__(self, engine: "ServingEngine", prompt: np.ndarray,
                 max_new_tokens: int, deadline: Optional[float],
                 seed: int):
        self._engine = engine
        self._cond = threading.Condition(engine._lock)
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.deadline = deadline            # absolute monotonic, or None
        self.seed = int(seed)
        self.status = "new"
        self.tokens: list = []
        self.t_tokens: list = []            # monotonic stamp per token
        self.error: Optional[BaseException] = None
        self.block_ids: tuple = ()
        self.t_submit = time.monotonic()
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        self.finish_reason: Optional[str] = None
        self.ttft: Optional[float] = None   # derived at _finish
        self.tpot: Optional[float] = None   # mean s/token past the first
        self.spec_proposed = 0              # draft tokens offered for us
        self.spec_accepted = 0              # ... accepted by the target
        self._cancel = False
        self.trace = telemetry.requestlog.RequestTrace(
            meta={"prompt_len": int(prompt.shape[0]),
                  "max_new_tokens": self.max_new_tokens,
                  "engine": engine._name})
        self.trace.event("submit", t=self.t_submit,
                         deadline_in=None if deadline is None
                         else round(deadline - self.t_submit, 6))

    @property
    def rid(self) -> int:
        """Process-unique request id (the trace ring's key)."""
        return self.trace.rid

    # -- engine side (engine lock held) ------------------------------- #
    def _deliver(self, tok: int, now: float) -> None:
        if self.t_first is None:
            self.t_first = now
        self.tokens.append(tok)
        self.t_tokens.append(now)
        self._cond.notify_all()

    def _finish(self, status: str, error: Optional[BaseException] = None):
        self.status = status
        self.error = error
        self.t_done = time.monotonic()
        if isinstance(error, RequestShed):
            self.finish_reason = error.reason
        elif isinstance(error, RequestTimedOut):
            self.finish_reason = "timeout"
        elif error is not None:
            self.finish_reason = status
        if self.t_first is not None:
            self.ttft = self.t_first - self.t_submit
            if len(self.tokens) > 1:
                self.tpot = (self.t_done - self.t_first) \
                    / (len(self.tokens) - 1)
        attrs = {"tokens": len(self.tokens)}
        if self.finish_reason is not None:
            attrs["reason"] = self.finish_reason
        if self.ttft is not None:
            attrs["ttft_s"] = round(self.ttft, 6)
        if self.tpot is not None:
            attrs["tpot_s"] = round(self.tpot, 6)
        if self.spec_proposed:
            attrs["spec_accept_rate"] = round(self.spec_accept_rate, 4)
        self.trace.event(status, t=self.t_done, **attrs)
        telemetry.requestlog.push(self.trace)
        self._cond.notify_all()

    # -- caller side --------------------------------------------------- #
    @property
    def finished(self) -> bool:
        return self.status in _TERMINAL

    @property
    def spec_accept_rate(self) -> float:
        """This request's draft-token acceptance rate (0.0 when it
        never ran under speculation)."""
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)

    def cancel(self) -> None:
        """Request cancellation (non-blocking, any thread, idempotent).
        A queued request is discarded; a running one is evicted at the
        next scheduler tick, freeing its KV blocks."""
        self._cancel = True
        eng = self._engine
        with eng._work:
            eng._queue_reap = True
            eng._work.notify_all()

    def result(self, timeout: Optional[float] = None) -> list:
        """Block until terminal; the generated token list, or raises
        the request's `ServingError` (shed/evicted/cancelled/failed)."""
        end = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self.status not in _TERMINAL:
                left = None if end is None else end - time.monotonic()
                if left is not None and left <= 0:
                    raise TimeoutError(
                        f"request not finished within {timeout}s "
                        f"(status={self.status})")
                self._cond.wait(_POLL_S if left is None
                                else min(_POLL_S, left))
            if self.error is not None:
                raise self.error
            return list(self.tokens)

    def stream(self):
        """Yield generated tokens as the engine emits them.  Exhausts
        on completion; raises the request's error on shed/evict/fail.
        Abandoning the generator (break / close / GC) cancels the
        request so its KV blocks return to the pool — tested by
        tests/test_serving.py::test_abandoned_stream_releases_blocks."""
        idx = 0
        try:
            while True:
                tok = None
                with self._cond:
                    while idx >= len(self.tokens) \
                            and self.status not in _TERMINAL:
                        self._cond.wait(_POLL_S)
                    if idx < len(self.tokens):
                        tok = self.tokens[idx]
                        idx += 1
                    elif self.error is not None:
                        raise self.error
                    else:
                        return
                yield tok
        finally:
            if not self.finished:
                self.cancel()


class _Slot:
    """Host bookkeeping of one occupied batch lane."""

    __slots__ = ("req", "blocks")

    def __init__(self, req: Request, blocks: list):
        self.req = req
        self.blocks = blocks


class _PrefillJob:
    """An admitted request's remaining prefill work: lane + blocks are
    already claimed (cache-hit prefix blocks bound read-only), the
    prompt tail past ``next_pos`` still needs chunking through the
    device.  The scheduler runs ONE chunk of ONE job per iteration,
    interleaved with decode steps."""

    __slots__ = ("lane", "req", "row", "key", "prompt", "P",
                 "cached_len", "next_pos", "t_work")

    def __init__(self, lane, req, row, key, prompt, P, cached_len):
        self.lane = lane
        self.req = req
        self.row = row
        self.key = key
        self.prompt = prompt
        self.P = P
        self.cached_len = cached_len
        self.next_pos = cached_len          # first unprefilled position
        self.t_work = 0.0                   # device seconds spent so far


class ServingEngine:
    """Continuous-batching decode over a decoder that describes itself
    (`net.decoder_spec()`, `models.generation.DecoderSpec`: a
    `models.TransformerLM`, or a decoder with recurrent layers such as
    `models.hybrid_ssm.HybridSSMDecoder`, whose per-lane recurrent state
    the engine keeps beside the paged K/V pool and for which speculation,
    int8 KV and prefix hits are not built; docs/serving.md, "Two kinds of
    state"; or one with sliding-window layers and routed experts such as
    `models.routed_window.RoutedWindowDecoder`, whose window layers keep
    a ring of pages a lane beside the block tables' pool and which is
    refused the same three; docs/serving.md, "Window layers and routed
    experts").

    Parameters (all static — changing them means a new engine):

    max_batch       decode lanes run per step (batch width).
    block_size      KV block width in positions (power of two).
    max_seq_len     cap on prompt+generated per request; defaults to
                    ``net._max_len`` rounded down to a block multiple.
    num_blocks      pool size; default fits ``max_batch`` full-length
                    sequences plus the scratch block.
    max_queue       admission queue bound (default 16).
    temperature/top_k/eos_id   sampling config (compiled into the
                    programs, as in `lm_generate`).
    ttft_budget     SLO seconds; estimated-late requests are shed.
    default_deadline   per-request deadline seconds (overridable per
                    submit).
    quantized       weight path selector, as in `lm_generate`.
    kv_dtype        KV pool dtype: None = model dtype, "int8" =
                    per-head symmetric int8 pages with fp32 scale
                    pools (quantized at page-write, dequantized inside
                    the paged-attention kernel) — ~2× the resident
                    sequences per HBM byte.
    attn_impl       paged-attention impl: None = auto (Pallas kernel
                    on TPU, PR 12's dense gather on CPU), or force
                    "pallas"/"dense" (tests, hlolint gate).
    prefill_chunk   prefill-chunk width in tokens (ISSUE 20): each
                    scheduler iteration runs at most ONE chunk of this
                    many prompt positions before the next decode step,
                    so a long arrival costs resident sequences one
                    chunk of latency per token, never a full prefill.
                    Default 32, clamped to ``max_seq_len``.  ONE chunk
                    program per engine — no pow2 bucket ladder, no
                    recompiles for unseen prompt lengths.
    speculate_k     speculative decoding window (ISSUE 19): a draft
                    model proposes k tokens per lane per scheduler
                    iteration and the target verifies all lanes'
                    windows in ONE batched donated forward, emitting
                    1..k+1 tokens per lane per weight stream.  Exact:
                    greedy decode stays bit-identical to
                    ``speculate_k=0``; stochastic sampling keeps the
                    target's output distribution (rejection
                    sampling + residual resample).  0 (default) = the
                    non-speculative scheduler, byte-for-byte the
                    pre-ISSUE-19 path.
    draft_net       the draft TransformerLM (same vocab, max_len >=
                    max_seq_len).  None with ``speculate_k>0``
                    self-drafts through the int8 weight path —
                    requires `net.quantize_for_decode` and a float
                    target.
    spec_greedy     force argmax prefix-match acceptance even at
                    temperature>0 (a throughput-over-sampling debug
                    knob; output becomes greedy).  temperature<=0
                    implies it.
    poll_interval   scheduler idle/wait tick (default 2 ms).
    fault_hook      callable(phase: str) invoked before each
                    "prefill"/"step" device call — the fault-injection
                    seam the load harness and tests use (sleep = slow
                    step, raise = scheduler failure).
    slo_ttft        TTFT target (s) for the burn-rate tracker (default
                    ``ttft_budget``, else 1.0 — the tracker is always
                    on so ``serving_slo_fraction{window=}`` always
                    exists).
    slo_tpot        mean-TPOT target (s); default None (off).
    slo_windows     burn-rate window lengths in seconds (default
                    (60, 600)); slo_objective the good-fraction target
                    (default 0.99, i.e. a 1% error budget).
    http_port       serve /metrics /healthz /varz /requestz /profilez
                    /stallz on this port (0 = ephemeral; read
                    ``engine.http_port`` back).  Default:
                    ``MXTPU_TELEMETRY_PORT`` if set, else no server.
                    close() joins the server.
    """

    @telemetry.profiler.setup_phased("engine")
    def __init__(self, net, *, max_batch: int = 4, block_size: int = 16,
                 max_seq_len: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: int = -1, ttft_budget: Optional[float] = None,
                 default_deadline: Optional[float] = None,
                 quantized=None, kv_dtype: Optional[str] = None,
                 attn_impl: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 speculate_k: int = 0, draft_net=None,
                 spec_greedy: bool = False,
                 poll_interval: Optional[float] = None,
                 fault_hook=None, slo_ttft: Optional[float] = None,
                 slo_tpot: Optional[float] = None,
                 slo_windows=None, slo_objective: float = 0.99,
                 http_port: Optional[int] = None):
        self._name = f"serving-{next(_engine_ids)}"
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if block_size < 1 or (block_size & (block_size - 1)):
            raise ValueError(
                f"block_size must be a power of two, got {block_size}")
        self._B = int(max_batch)
        self._bs = int(block_size)
        # the device side — programs, pools, recurrent state, weights —
        # is this object's; it also resolves the sizes that depend on
        # the net's description (max_seq_len in whole blocks, the pool's
        # default size, the chunk clamped to a sequence)
        self._programs = PagedPrograms(
            net, max_batch=self._B, block_size=self._bs,
            max_seq_len=max_seq_len, num_blocks=num_blocks,
            temperature=temperature, top_k=top_k, quantized=quantized,
            kv_dtype=kv_dtype, attn_impl=attn_impl,
            prefill_chunk=prefill_chunk if prefill_chunk is not None
            else _PREFILL_CHUNK, speculate_k=speculate_k,
            draft_net=draft_net, spec_greedy=spec_greedy)
        self._msl = self._programs.max_seq_len
        self._nbps = self._msl // self._bs
        self._num_blocks = self._programs.num_blocks
        self._chunk = self._programs.prefill_chunk_len
        self._spec_k = int(speculate_k)
        self._spec = self._spec_k > 0
        self._path = self._programs.path          # "float" / "int8"
        self._kv_dtype = self._programs.kv_dtype
        # a recurrent layer's state cannot be handed to a prefix hit, nor
        # can a window layer's ring, which has given the prefix's pages
        # away
        spec = self._programs.spec
        self._recurrent = spec.recurrent
        self._carried = spec.carried
        self._window = spec.window
        self._moe = spec.moe
        # routed experts held here, of those the router scores (0, 0: none)
        self._share = (spec.moe.held, spec.moe.experts) \
            if spec.moe is not None else (0, 0)
        self._state_resets = 0          # first chunks since the last record
        # the experts' counts the last landed step brought with its tokens,
        # and behind them an index's (positions scored, positions attended)
        self._expert_counts = (0, 0, 0)
        self._index = spec.index
        self._index_counts = (0, 0)
        self._max_queue = int(max_queue if max_queue is not None
                              else _MAX_QUEUE)
        self._eos = int(eos_id)
        self._ttft_budget = ttft_budget
        self._default_deadline = default_deadline
        self._poll = float(poll_interval if poll_interval is not None
                           else _POLL_S)
        self._fault_hook = fault_hook
        self._pool = BlockPool(self._num_blocks, self._bs)
        if telemetry.enabled():
            telemetry.gauge("serving_kv_bytes_per_token",
                            labels={"engine": self._name}) \
                .set(self.kv_bytes_per_token)
            telemetry.gauge("serving_state_bytes_per_seq",
                            labels={"engine": self._name}) \
                .set(self.state_bytes_per_seq)
            telemetry.gauge("serving_window_pool_bytes",
                            labels={"engine": self._name}) \
                .set(self.window_pool_bytes)
            telemetry.gauge("serving_index_pool_bytes",
                            labels={"engine": self._name}) \
                .set(self.index_pool_bytes)
            telemetry.gauge("serving_experts_held",
                            labels={"engine": self._name}) \
                .set(self._share[0])
            impl = self._programs.attn_impl
            for path in ("pallas", "dense"):
                telemetry.gauge("paged_attn_kernel",
                                labels={"path": path}) \
                    .set(1.0 if path == impl else 0.0)
            for form in ("window", "lanes", "dense"):
                telemetry.gauge("paged_attn_chunk",
                                labels={"form": form}) \
                    .set(1.0 if form == self._programs.chunk_attn else 0.0)

        # per-lane step inputs (scheduler thread only; snapshots are
        # passed to the program, so the jit never closes over state)
        B, nbps = self._B, self._nbps
        self._tables = np.full((B, nbps), SCRATCH_BLOCK, np.int32)
        self._toks = np.zeros((B,), np.int32)
        self._pos = np.zeros((B,), np.int32)
        self._active = np.zeros((B,), bool)
        self._keys = np.zeros((B, 2), np.uint32)
        # lanes whose `_toks` entry a prompt's final chunk set since the
        # last step was handed over: the step takes their token from the
        # host, every other lane's from the step before, on the device
        self._fresh = np.zeros((B,), bool)
        # the position at which a lane needs no further step: its last
        # token by count (`max_new_tokens`) is then delivered or in flight
        self._end = np.zeros((B,), np.int32)
        # the step handed over whose tokens are not read yet (scheduler
        # thread only): (tokens on the device, its lanes, when, 1 where
        # the step before it was still unread); at most one
        self._flying = None
        self._slots: list = [None] * B

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._queue: deque = deque()
        # whether the queue may hold a request to reap (a deadline, a
        # cancel): a backlog of thousands is not walked every iteration
        self._queue_reap = False
        # admitted-but-unprefilled work, oldest first: each entry is a
        # _PrefillJob whose lane+blocks are already claimed; the
        # scheduler runs one chunk of the head job per iteration
        self._prefill_jobs: deque = deque()
        self._stop = threading.Event()
        self._closed = False
        self._err_lock = threading.Lock()
        self._pending_err: Optional[BaseException] = None
        self._prefill_ewma: Optional[float] = None
        self._stats = {"admitted": 0, "done": 0, "steps": 0,
                       "steps_ahead": 0, "prefix_hits": 0, "prefix_misses": 0,
                       "cached_tokens": 0,
                       "shed": OrderedDict(), "evicted": OrderedDict()}
        if self._spec:
            self._stats.update(spec_steps=0, spec_proposed=0,
                               spec_accepted=0, spec_ewma=None,
                               spec_rollback=OrderedDict())
        self._last_tick = time.monotonic()   # scheduler liveness heartbeat

        # SLO burn-rate tracker: always on (host-side booleans; the
        # gauges it feeds still honour the telemetry disabled path)
        if slo_ttft is None:
            slo_ttft = float(ttft_budget if ttft_budget is not None
                             else _SLO_TTFT_S)
        self._slo = telemetry.slo.SloTracker(
            ttft_target=slo_ttft, tpot_target=slo_tpot,
            windows=slo_windows if slo_windows is not None
            else telemetry.slo.DEFAULT_WINDOWS,
            objective=slo_objective)

        # ops endpoint: explicit port wins, else MXTPU_TELEMETRY_PORT,
        # else no server.  Best-effort — a taken port degrades to None
        # (a second engine in the process) instead of killing serving.
        self._http: Optional[telemetry.http.TelemetryServer] = None
        if http_port is None:
            self._http = telemetry.http.start_from_env()
        else:
            try:
                self._http = telemetry.http.TelemetryServer(
                    port=int(http_port))
            except OSError:
                self._http = None
        if self._http is not None:
            self._http.register_health(self._name, self.health)
            self._http.register_requestz(self._name, self.requestz)
            self._http.register_varz(self._name, self.varz_config)
        # SIGTERM/crash bundles carry the in-flight table + trace ring
        telemetry.flight_recorder.register_section(
            self._name, self._flight_section)
        # per-iteration stall-attribution ledger (ISSUE 17, 28): always
        # constructed and fed by the scheduler loop — disabling
        # (MXTPU_SERVING_PROFILER=0 / set_enabled(False)) leaves one
        # flag read per phase.  Registered process-wide so /profilez
        # and /stallz see every live engine's lane; its records stay in
        # the process's ring (telemetry.profiler.iterations) after
        # close().
        self._prof = telemetry.profiler.register(
            telemetry.profiler.EngineProfiler(self._name))
        telemetry.profiler.install_gc_hooks()

        self._thread = threading.Thread(
            target=self._scheduler, daemon=True,
            name="mxtpu-serving-scheduler")
        self._thread.start()

    # ------------------------------------------------------------------ #
    # public API
    # ------------------------------------------------------------------ #
    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def max_seq_len(self) -> int:
        return self._msl

    @property
    def kv_dtype(self) -> Optional[str]:
        """None (model dtype) or "int8"."""
        return self._kv_dtype

    @property
    def attn_impl(self) -> str:
        """Resolved paged-attention impl ("pallas" / "dense")."""
        return self._programs.attn_impl

    @property
    def kv_pool_bytes(self) -> int:
        """Device bytes of the whole KV pool (pages + int8 scales,
        all layers, and the index keys' pools of a decoder that has an
        index) — the denominator of the int8 capacity win.
        Frozen at construction: donation swaps the pool arrays every
        step but never their shapes."""
        return self._programs.kv_pool_bytes

    @property
    def window_pool_bytes(self) -> int:
        """Device bytes of the window layers' rings (K and V, every window
        layer, `window_blocks` a lane and the scratch block): whatever
        `max_seq_len`; 0 for a decoder without window layers.  Not part of
        `kv_pool_bytes`, which is the pool the block tables name."""
        return self._programs.window_pool_bytes

    @property
    def index_pool_bytes(self) -> int:
        """Device bytes of the index keys' pools (every layer with an
        index; a row a position, named by the block tables as K's and
        V's): part of `kv_pool_bytes`, so a block's and a token's bytes
        count all three arrays; 0 for a decoder without an index."""
        return self._programs.index_pool_bytes

    @property
    def state_bytes(self) -> int:
        """Device bytes of the recurrent state (float32 states and conv
        windows, every ssm layer, every lane); 0 for a decoder of
        attention layers only.  Whatever the sequences' lengths."""
        return self._programs.state_bytes

    @property
    def state_bytes_per_seq(self) -> int:
        """Recurrent-state bytes one lane holds — the
        `serving_state_bytes_per_seq` gauge's value."""
        return self.state_bytes // self._B

    @property
    def kv_block_bytes(self) -> int:
        """Pool bytes one block costs across all layers (K + V +
        scales + index keys); `kv_pool_bytes == num_blocks *
        kv_block_bytes`."""
        return self.kv_pool_bytes // self._num_blocks

    @property
    def kv_bytes_per_token(self) -> int:
        """Pool bytes one token position costs across all layers —
        the `serving_kv_bytes_per_token` gauge's value."""
        return self.kv_block_bytes // self._bs

    @property
    def http(self) -> Optional["telemetry.http.TelemetryServer"]:
        """The engine's ops endpoint server, or None (not configured /
        port taken)."""
        return self._http

    @property
    def http_port(self) -> Optional[int]:
        """Bound port of the ops endpoint (useful with port 0)."""
        return self._http.port if self._http is not None else None

    @property
    def slo(self) -> "telemetry.slo.SloTracker":
        return self._slo

    def health(self) -> dict:
        """Liveness + headroom + SLO burn, the `/healthz` payload.

        status semantics (worst check wins):

        * ``unhealthy`` — stop routing traffic here: the engine is
          closed, the scheduler thread died, or a scheduler error is
          parked (every submit will raise).
        * ``degraded``  — serving but at the edge: admission queue at
          capacity, zero free KV blocks, the scheduler heartbeat is
          stale, or the fast SLO window is burning error budget
          (burn rate > 1).
        * ``healthy``   — everything above holds headroom.
        """
        now = time.monotonic()
        with self._work:        # same lock the scheduler's tick writes under
            qd = len(self._queue)
            active = int(self._active.sum())
            free = self._pool.num_free
            tick_age = now - self._last_tick
        alive = self._thread.is_alive()
        parked = self._has_pending_err()
        burning = any(r > 1.0 for r in self._slo.burn_rates(now).values())
        checks = {
            "scheduler": {
                "status": "unhealthy" if (parked or not alive) else
                          ("degraded" if tick_age > max(2.0, 500 * self._poll)
                           else "healthy"),
                "alive": alive, "parked_error": parked,
                "tick_age_s": round(tick_age, 4)},
            "queue": {
                "status": "degraded" if qd >= self._max_queue else "healthy",
                "depth": qd, "max": self._max_queue},
            "kv_blocks": {
                "status": "degraded" if free == 0 else "healthy",
                "free": free, "total": self._num_blocks - 1,
                "active_lanes": active, "max_batch": self._B},
            "slo": {
                "status": "degraded" if burning else "healthy",
                **self._slo.snapshot(now)},
        }
        if self._closed:
            checks["scheduler"]["status"] = "unhealthy"
            checks["scheduler"]["closed"] = True
        order = {"healthy": 0, "degraded": 1, "unhealthy": 2}
        status = max((c["status"] for c in checks.values()),
                     key=lambda s: order[s])
        return {"status": status, "engine": self._name,
                "path": self._path,
                "kv_dtype": self._kv_dtype or "model",
                "attn_impl": self._programs.attn_impl,
                "checks": checks}

    def requestz(self) -> dict:
        """Currently queued + running requests (the `/requestz`
        in-flight table; completed traces live in the requestlog ring)."""
        now = time.monotonic()
        rows = []
        with self._lock:
            for req in self._queue:
                rows.append(self._request_row(req, now, lane=None))
            for i, slot in enumerate(self._slots):
                if slot is not None:
                    rows.append(self._request_row(slot.req, now, lane=i))
            stats = {"admitted": self._stats["admitted"],
                     "done": self._stats["done"],
                     "steps": self._stats["steps"],
                     "queue_depth": len(self._queue),
                     "blocks_free": self._pool.num_free,
                     "kv_pool_bytes": self.kv_pool_bytes,
                     "state_bytes": self.state_bytes,
                     "prefill_chunks_pending":
                         self._pending_chunks_locked(),
                     "prefix_cache": {
                         "hits": self._stats["prefix_hits"],
                         "misses": self._stats["prefix_misses"],
                         **self._pool.prefix_stats()}}
        return {"engine": self._name, "path": self._path,
                "in_flight": rows, "stats": stats,
                "slo": self._slo.snapshot(now)}

    @staticmethod
    def _request_row(req: Request, now: float, lane) -> dict:
        row = {"rid": req.rid, "status": req.status,
               "age_s": round(now - req.t_submit, 4),
               "prompt_len": int(req.prompt.shape[0]),
               "max_new_tokens": req.max_new_tokens,
               "tokens": len(req.tokens)}
        if lane is not None:
            row["lane"] = lane
            row["blocks"] = list(req.block_ids)
        if req.deadline is not None:
            row["deadline_in_s"] = round(req.deadline - now, 4)
        if req.t_first is not None:
            row["ttft_s"] = round(req.t_first - req.t_submit, 6)
        return row

    def _spec_section(self) -> Optional[dict]:
        """Speculation config + live acceptance EWMA for `/varz` and
        the flight recorder — post-mortem bundles must explain a
        throughput delta without guessing the engine's draft setup.
        None when speculation is off."""
        if not self._spec:
            return None
        return {"k": self._spec_k,
                "draft": self._programs.draft_label,
                "greedy": self._programs.spec_greedy,
                "accept_rate_ewma":
                    None if self._stats["spec_ewma"] is None
                    else round(self._stats["spec_ewma"], 4)}

    def _flight_section(self) -> dict:
        """Flight-recorder dump hook.  Runs inside a signal handler on
        whatever thread holds whatever locks — so it TRIES the engine
        lock instead of deadlocking when the signal lands inside a
        locked region of this very thread."""
        if not self._lock.acquire(timeout=0.5):
            return {"engine": self._name,
                    "error": "engine lock busy at dump time"}
        try:
            now = time.monotonic()
            rows = [self._request_row(r, now, lane=None)
                    for r in self._queue]
            rows += [self._request_row(s.req, now, lane=i)
                     for i, s in enumerate(self._slots) if s is not None]
            stats = {"admitted": self._stats["admitted"],
                     "done": self._stats["done"],
                     "steps": self._stats["steps"],
                     "shed": dict(self._stats["shed"]),
                     "evicted": dict(self._stats["evicted"]),
                     "prefill_chunks_pending":
                         self._pending_chunks_locked(),
                     "prefix_cache": {
                         "hits": self._stats["prefix_hits"],
                         "misses": self._stats["prefix_misses"],
                         **self._pool.prefix_stats()}}
        finally:
            self._lock.release()
        return {"engine": self._name, "in_flight": rows, "stats": stats,
                "speculate": self._spec_section(),
                "slo": self._slo.snapshot(now),
                "stalls": self._prof.recent_stalls(8),
                "recent_steps": self._prof.recent_steps(8),
                "recent_traces": telemetry.requestlog.recent(32)}

    @property
    def profiler(self) -> "telemetry.profiler.EngineProfiler":
        """The engine's per-step stall-attribution ledger."""
        return self._prof

    def capture_profile(self, seconds: float = 1.0) -> dict:
        """On-demand merged timeline capture (the `/profilez` payload):
        let ``seconds`` of serving activity accumulate, then return one
        chrome-trace dict with request, scheduler, program, GC and
        lock-contention lanes (0 = everything still buffered)."""
        return telemetry.profiler.capture(seconds)

    def stall_table(self) -> list:
        """Aggregate stall attribution rows (cause / total_s / share /
        per_step_ms), biggest cause first."""
        return self._prof.stall_table()

    def stallz(self) -> dict:
        """This engine's `/stallz` payload: cause table + worst recent
        hiccups with their per-cause ledgers."""
        return self._prof.stallz()

    def _sparse_runs(self) -> dict:
        walked, table = self._programs.sparse_runs
        return {"sparse_runs_walked": walked, "sparse_runs_table": table}

    def varz_config(self) -> dict:
        """Build/config section for `/varz` — which engine
        configuration is actually running (ops triage can't tell from
        metrics alone).  Values are frozen at construction except the
        profiler toggle, MXTPU_* env knobs and the sparse kernels' run
        counts, read live."""
        with self._lock:    # spec_ewma is written under the tick lock
            spec = self._spec_section()
        return {
            "engine": self._name,
            "path": self._path,
            "prog_label": self._programs.prog_label,
            "kv_dtype": self._kv_dtype or "model",
            "attn_impl": self._programs.attn_impl,
            "paged_pages_per_step": self._programs.pages_per_step,
            # how a prefill chunk attends the sequence's pages: "window"
            # (once for all its queries), "lanes" (once a query) or "dense"
            "chunk_attn": self._programs.chunk_attn,
            # how a layer with an index selects: "kernel", "xla" or "none"
            "index_select": self._programs.index_select,
            # runs the sparse kernels' loops visited (a sequence's live
            # runs, a row tile each) and the grid steps a grid over every
            # run of the table would have taken (0, 0: no kernel walks)
            **self._sparse_runs(),
            "max_batch": self._B,
            "block_size": self._bs,
            "max_seq_len": self._msl,
            "num_blocks": self._num_blocks,
            "max_queue": self._max_queue,
            "prefill_chunk": self._chunk,
            # decode steps the scheduler hands over before it reads their
            # tokens (a speculating engine reads each window first)
            "steps_in_flight": 0 if self._spec else 1,
            # a recurrent layer's state cannot be handed to a prefix hit,
            # nor a window layer's ring
            "prefix_cache": not self._carried,
            "kv_pool_bytes": self.kv_pool_bytes,
            "window_pool_bytes": self.window_pool_bytes,
            # of kv_pool_bytes, the index keys' pools; the positions a
            # query attends of those its index scores (0, 0: no index)
            "index_pool_bytes": self.index_pool_bytes,
            "index_topk": self._index.topk if self._index else 0,
            # 0: every attention layer attends every earlier position
            "attention_window": self._window,
            "window_blocks_per_lane": self._programs.window_blocks,
            # routed experts: those this engine's net holds, of those its
            # router scores (0, 0: no routed layer)
            "experts_held": self._share[0],
            "experts_published": self._share[1],
            "state_bytes": self.state_bytes,
            "state_bytes_per_seq": self.state_bytes_per_seq,
            "speculate": spec,
            "eos_id": self._eos,
            "poll_interval_s": self._poll,
            "ttft_budget_s": self._ttft_budget,
            "default_deadline_s": self._default_deadline,
            "slo": {"ttft_target_s": self._slo.ttft_target,
                    "tpot_target_s": self._slo.tpot_target,
                    "objective": self._slo.objective,
                    "windows_s": list(self._slo.windows)},
            "profiler": {"enabled": self._prof.enabled,
                         "hiccup_k": self._prof.hiccup_k},
            "env": {k: v for k, v in sorted(os.environ.items())
                    if k.startswith("MXTPU_")},
        }

    def set_fault_hook(self, hook) -> None:
        with self._lock:
            self._fault_hook = hook

    def set_ttft_budget(self, seconds: Optional[float]) -> None:
        with self._lock:
            self._ttft_budget = seconds

    def submit(self, prompt, max_new_tokens: int, *,
               deadline: Optional[float] = None, seed: int = 0,
               block: bool = False,
               timeout: Optional[float] = None) -> Request:
        """Enqueue a generation request; returns its `Request` handle
        immediately (inspect ``.status`` / call ``.result()``).

        ``deadline`` is seconds from now (default the engine's
        ``default_deadline``); a queue-full engine SHEDS the request
        (``block=False``, the open-loop default) or waits for space up
        to ``timeout`` (``block=True``) — waiting observes `close()`.
        """
        prompt = self._as_prompt(prompt)
        P = prompt.shape[0]
        N = int(max_new_tokens)
        if N < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {N}")
        if P < 1:
            raise ValueError("prompt must be non-empty")
        if P + N > self._msl:
            raise ValueError(
                f"prompt+new = {P + N} exceeds max_seq_len {self._msl}")
        if self._blocks_needed(P, N) > self._num_blocks - 1:
            raise ValueError(
                f"request needs {self._blocks_needed(P, N)} KV blocks "
                f"but the pool only has {self._num_blocks - 1} — it "
                "could never be admitted")
        if deadline is None:
            deadline = self._default_deadline
        abs_deadline = None if deadline is None \
            else time.monotonic() + float(deadline)
        req = Request(self, prompt, N, abs_deadline, seed)
        end = None if timeout is None else time.monotonic() + timeout
        with self._work:
            self._check_alive()
            while len(self._queue) >= self._max_queue:
                if not block:
                    self._shed_locked(req, "queue_full")
                    return req
                left = None if end is None else end - time.monotonic()
                if left is not None and left <= 0:
                    self._shed_locked(req, "queue_full")
                    return req
                self._work.wait(self._poll if left is None
                                else min(self._poll, left))
                self._check_alive()
            req.status = "queued"
            self._queue.append(req)
            if abs_deadline is not None:
                self._queue_reap = True
            req.trace.event("queued", queue_depth=len(self._queue))
            self._note_queue_depth_locked()
            self._work.notify_all()
        return req

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait until the queue is empty and every lane idle; True on
        success, False on timeout (work still in flight)."""
        end = None if timeout is None else time.monotonic() + timeout
        with self._work:
            while self._queue or any(s is not None for s in self._slots):
                if self._has_pending_err() or self._closed:
                    return not (self._queue
                                or any(s is not None for s in self._slots))
                left = None if end is None else end - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._work.wait(self._poll if left is None
                                else min(self._poll, left))
            return True

    def close(self, timeout: Optional[float] = 10.0) -> None:
        """Stop and JOIN the scheduler thread (and the ops HTTP
        server), abort any unfinished requests (their handles see
        `RequestCancelled`), release all blocks, and re-raise a parked
        scheduler error (idempotent)."""
        with self._work:
            already = self._closed
            self._closed = True
            self._stop.set()
            self._work.notify_all()
        if not already:
            self._thread.join(timeout)
            with self._work:
                self._abort_all_locked(
                    RequestCancelled("serving engine closed"))
                self._work.notify_all()
            telemetry.flight_recorder.unregister_section(self._name)
            telemetry.profiler.unregister(self._name)
            if self._http is not None:
                self._http.unregister(self._name)
                self._http.close(timeout)
        with self._err_lock:
            err, self._pending_err = self._pending_err, None
        if err is not None:
            raise RequestFailed("serving scheduler failed") from err

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def stats(self) -> dict:
        """Snapshot of the engine's counters (host-side, lock-held)."""
        with self._lock:
            out = {
                "admitted": self._stats["admitted"],
                "done": self._stats["done"],
                "steps": self._stats["steps"],
                # of them, those handed over while the step before was
                # still unread (the decode loop runs one step ahead)
                "steps_ahead": self._stats["steps_ahead"],
                "shed": dict(self._stats["shed"]),
                "evicted": dict(self._stats["evicted"]),
                "queue_depth": len(self._queue),
                "active": int(self._active.sum()),
                "blocks_free": self._pool.num_free,
                "blocks_total": self._num_blocks - 1,
                "kv_pool_bytes": self.kv_pool_bytes,
                "window_pool_bytes": self.window_pool_bytes,
                "index_pool_bytes": self.index_pool_bytes,
                "state_bytes": self.state_bytes,
                **self._sparse_runs(),
                "prefix_cache": {
                    "hits": self._stats["prefix_hits"],
                    "misses": self._stats["prefix_misses"],
                    "cached_tokens": self._stats["cached_tokens"],
                    **self._pool.prefix_stats()},
                "prefill_chunk": {
                    "chunk": self._chunk,
                    "jobs": len(self._prefill_jobs),
                    "pending_chunks": self._pending_chunks_locked()},
            }
            if self._spec:
                prop = self._stats["spec_proposed"]
                out["speculate"] = {
                    "k": self._spec_k,
                    "draft": self._programs.draft_label,
                    "steps": self._stats["spec_steps"],
                    "proposed": prop,
                    "accepted": self._stats["spec_accepted"],
                    "accept_rate": (self._stats["spec_accepted"] / prop
                                    if prop else None),
                    "accept_rate_ewma": self._stats["spec_ewma"],
                    "rollback": dict(self._stats["spec_rollback"]),
                }
            return out

    # ------------------------------------------------------------------ #
    # internals
    # ------------------------------------------------------------------ #
    @staticmethod
    def _as_prompt(prompt) -> np.ndarray:
        from ..ndarray.ndarray import NDArray

        if isinstance(prompt, NDArray):
            prompt = prompt._data
        arr = np.asarray(prompt, np.int32)
        if arr.ndim == 2 and arr.shape[0] == 1:
            arr = arr[0]
        if arr.ndim != 1:
            raise ValueError(
                f"prompt must be 1-D (or (1, P)), got shape {arr.shape}")
        return arr

    def _has_pending_err(self) -> bool:
        with self._err_lock:
            return self._pending_err is not None

    def _check_alive(self) -> None:
        with self._err_lock:
            err = self._pending_err
        if err is not None:
            raise RequestFailed("serving scheduler failed") from err
        if self._closed:
            raise RuntimeError("serving engine is closed")

    def _blocks_needed(self, P: int, N: int) -> int:
        horizon = P + N
        if self._spec:
            # the speculative window writes up to k positions past the
            # last committed one: the last committed position is at
            # most P+N-2 (the final token needs no write), so the
            # worst-case write sits at min(P+N-2+k, msl-1) — reserve
            # blocks covering it so rejected-position garbage always
            # lands in the lane's OWN pages, never a neighbour's
            horizon = min(P + N - 1 + self._spec_k, self._msl)
        return -(-horizon // self._bs)

    def _count(self, table: OrderedDict, reason: str) -> None:
        table[reason] = table.get(reason, 0) + 1

    def _note_queue_depth_locked(self) -> None:
        if telemetry.enabled():
            telemetry.gauge("serving_queue_depth").set(len(self._queue))

    def _shed_locked(self, req: Request, reason: str) -> None:
        req._finish("shed", RequestShed(reason))
        self._count(self._stats["shed"], reason)
        self._slo.note_bad()
        self._slo.observe()
        if telemetry.enabled():
            telemetry.counter("serving_shed_total",
                              labels={"reason": reason}).inc()

    def _abort_all_locked(self, error: BaseException) -> None:
        self._prefill_jobs.clear()
        while self._queue:
            self._queue.popleft()._finish("cancelled", error)
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            self._release_lane_locked(i)
            slot.req._finish("cancelled", error)
        self._note_queue_depth_locked()

    def _release_lane_locked(self, i: int) -> None:
        slot = self._slots[i]
        self._pool.free(slot.blocks)        # decref: shared prefix
        self._slots[i] = None               # blocks survive in-cache
        self._tables[i, :] = SCRATCH_BLOCK
        self._active[i] = False
        self._fresh[i] = False
        self._toks[i] = 0
        self._pos[i] = 0
        self._end[i] = 0
        if telemetry.enabled():
            telemetry.gauge("serving_kv_blocks_in_use") \
                .set(self._pool.num_allocated)
            telemetry.gauge("serving_kv_blocks_shared") \
                .set(self._pool.num_shared)

    def _evict_locked(self, i: int, reason: str,
                      error: BaseException) -> None:
        req = self._slots[i].req
        self._release_lane_locked(i)
        req._finish("cancelled" if reason == "cancel" else "evicted",
                    error)
        self._count(self._stats["evicted"], reason)
        if reason != "cancel":              # user cancels are SLO-neutral
            self._slo.note_bad()
            self._slo.observe()
        if telemetry.enabled():
            telemetry.counter("serving_evicted_total",
                              labels={"reason": reason}).inc()

    # -- scheduler thread ---------------------------------------------- #
    def _scheduler(self) -> None:
        try:
            self._run()
        finally:
            # an engine whose scheduler has ended serves nothing: let go
            # of what it held on the device (pools, recurrent state, the
            # gathered weights, the nets), here on the one thread that
            # writes them, so that a `Request` handle someone still holds
            # does not keep a model's memory alive through its engine
            self._programs.release()

    def _run(self) -> None:
        try:
            self._loop()
        except BaseException as e:
            with self._err_lock:
                self._pending_err = e
            # tokens of a step already handed over are the requests': they
            # are read and delivered before the requests are failed
            try:
                self._land_step()
            except Exception:   # the device is what failed: `e` says it
                pass
            failure = RequestFailed("serving scheduler failed")
            failure.__cause__ = e
            with self._work:
                self._prefill_jobs.clear()
                while self._queue:
                    self._queue.popleft()._finish("failed", failure)
                for i, slot in enumerate(self._slots):
                    if slot is not None:
                        self._release_lane_locked(i)
                        slot.req._finish("failed", failure)
                self._note_queue_depth_locked()
                self._work.notify_all()

    def _loop(self) -> None:
        # every phase of the iteration runs inside `prof.phase(cause)`:
        # lock acquisition, reap+admission bookkeeping, idle polls — so
        # the per-step causes sum to the step's wall time (profiler.py).
        # Iteration shape (ISSUE 20): reap → admit everything that fits
        # (lanes + blocks claimed, prefix blocks bound) → run at most
        # ONE prefill chunk → run ONE decode step over live lanes.
        # Interleaving chunk and decode per iteration is what bounds a
        # resident sequence's tpot spike to one chunk of compute.
        #
        # The decode loop runs ONE step ahead of its reads (ISSUE 35,
        # docs/serving.md "One step ahead"): iteration k hands over
        # chunk k and step k, and only then reads and commits step k-1,
        # then commits chunk k.  Step k needs nothing of that read: a
        # lane's token is step k-1's output where it lies on the device,
        # its position is one further, and a lane that ends by count
        # leaves the snapshot by `_end`; so the host's bookkeeping and
        # dispatch run while the device works.  Known one step late: an
        # EOS, a cancel or deadline, a freed lane.
        prof = self._prof
        while True:
            with prof.phase("lock_wait"), self._work, \
                    prof.phase("bookkeeping"):
                if self._stop.is_set():
                    break
                now = time.monotonic()
                self._last_tick = now       # health(): liveness heartbeat
                self._reap_locked(now)
                while self._admit_locked(now):
                    pass
                staged = self._stage_chunk_locked()
                # lanes the next step runs: a lane at its `_end` has its
                # last token delivered or in flight
                stepping = self._active & (self._pos < self._end)
                live = [(i, s.req) for i, s in enumerate(self._slots)
                        if s is not None and stepping[i]]
                snap = (self._tables.copy(), self._toks.copy(),
                        self._pos.copy(), stepping,
                        self._keys.copy()) if live else None
                if live and not self._spec:
                    # what the NEXT hand-over needs is settled here, not
                    # at the commit: it needs no token
                    snap += (self._fresh.copy(),)
                    self._fresh[:] = False
                    self._pos[stepping] += 1
                hook = self._fault_hook
                if staged is None and not live and self._flying is None:
                    if not self._queue:
                        with prof.phase("wait"):
                            self._work.wait(self._poll)
                    continue
            # the chunk is handed to the device and committed only once
            # the decode step has been handed over behind it: the step's
            # lanes were snapshotted above, so it does not need the
            # chunk's first token, and its dispatch then runs while the
            # device works on the chunk, a prompt's last chunk included
            chunk = self._run_chunk(staged, hook) \
                if staged is not None else None
            if self._spec:
                if chunk is not None:
                    self._commit_chunk(chunk)
                if live:
                    self._spec_step(snap, live, hook)
            else:
                # with no lane live this only lands the step in flight:
                # its lanes are freed (drain() waits on that) before the
                # scheduler idles
                self._decode_step(snap, live, hook)
                if chunk is not None:
                    self._commit_chunk(chunk)
        self._land_step()       # stopped: no token handed over is lost

    def _reap_locked(self, now: float) -> None:
        # queued requests: cancellation and deadlines apply while waiting
        if self._queue and self._queue_reap:
            keep = deque()
            self._queue_reap = False
            for req in self._queue:
                if req._cancel:
                    req._finish("cancelled", RequestCancelled("cancelled"))
                elif req.deadline is None:
                    keep.append(req)
                elif now > req.deadline:
                    self._shed_locked(req, "deadline")
                else:
                    keep.append(req)
                    self._queue_reap = True     # one to look at again
            if len(keep) != len(self._queue):
                # mutate in place: the deque identity is shared with
                # every lock-holding reader (submit/stats/drain)
                self._queue.clear()
                self._queue.extend(keep)
                self._note_queue_depth_locked()
                self._work.notify_all()     # queue space freed
        # running lanes: evict mid-batch (blocks freed, neighbours
        # untouched — see docs/serving.md for why this is exact)
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            if slot.req._cancel:
                self._evict_locked(i, "cancel",
                                   RequestCancelled("cancelled"))
            elif slot.req.deadline is not None \
                    and now > slot.req.deadline:
                self._evict_locked(
                    i, "timeout",
                    RequestTimedOut(f"deadline exceeded after "
                                    f"{len(slot.req.tokens)} token(s)"))

    def _admit_locked(self, now: float) -> bool:
        """Admit the queue head: claim a lane, look the prompt up in
        the prefix cache, bind the cache-hit blocks copy-on-write, and
        alloc private blocks for the tail — all under the lock.  The
        remaining prefill work is queued as a `_PrefillJob` (chunks run
        OUTSIDE the lock, one per scheduler iteration).  Returns False
        when nothing is admissible (empty queue, batch full, pool
        full)."""
        while self._queue:
            req = self._queue[0]
            if self._ttft_budget is not None \
                    and self._prefill_ewma is not None:
                est = (now - req.t_submit) + self._prefill_ewma
                if est > self._ttft_budget:
                    self._queue.popleft()
                    self._shed_locked(req, "slo")
                    self._note_queue_depth_locked()
                    self._work.notify_all()
                    continue
            try:
                lane = self._slots.index(None)
            except ValueError:
                return False                # batch full
            P = req.prompt.shape[0]
            needed = self._blocks_needed(P, req.max_new_tokens)
            # prefix-cache lookup + COW bind: bound blocks are never
            # written by this request (chunks start at cached_len,
            # decode writes at >= P), so sharing needs no copy
            # (a decoder with recurrent or window layers has no state to
            # hand a hit: the lookup is a miss, counted as one —
            # docs/serving.md)
            hits, cached_len = ([], 0) if self._carried \
                else self._pool.lookup(req.prompt)
            self._pool.bind(hits)
            fresh = self._pool.alloc(needed - len(hits))
            if fresh is None:
                self._pool.unbind(hits)     # roll back: FCFS head waits
                return False
            blocks = list(hits) + fresh
            # register the lane BEFORE any (unlocked) chunk runs: if a
            # chunk or a fault hook raises, the scheduler failure path
            # finds the request in its slot and finishes it — no
            # handle ever hangs
            self._queue.popleft()
            self._slots[lane] = _Slot(req, blocks)
            req.block_ids = tuple(blocks)
            row = np.full((self._nbps,), SCRATCH_BLOCK, np.int32)
            row[:len(blocks)] = blocks
            key = np.array([(req.seed >> 32) & 0xFFFFFFFF,
                            req.seed & 0xFFFFFFFF], np.uint32)
            n_chunks = -(-(P - cached_len) // self._chunk)
            self._stats["prefix_hits" if cached_len else
                        "prefix_misses"] += 1
            self._stats["cached_tokens"] += cached_len
            req.trace.event("admitted", lane=lane,
                            blocks=[int(b) for b in blocks],
                            cached_tokens=cached_len, chunks=n_chunks,
                            queue_wait_s=round(
                                time.monotonic() - req.t_submit, 6))
            # req.prompt is already a host np.int32 array (submit()
            # runs _as_prompt before taking the lock) — no conversion
            # here, nothing under _lock may dispatch or sync
            self._prefill_jobs.append(_PrefillJob(
                lane, req, row, key, req.prompt, P, cached_len))
            if telemetry.enabled():
                telemetry.counter(
                    "serving_prefix_cache_hits_total" if cached_len
                    else "serving_prefix_cache_misses_total").inc()
                telemetry.gauge("serving_kv_blocks_shared") \
                    .set(self._pool.num_shared)
                self._note_chunk_queue_locked()
            self._note_queue_depth_locked()
            self._work.notify_all()         # queue space freed
            return True
        return False

    def _stage_chunk_locked(self):
        """Pick the next prefill chunk to run: the oldest job whose
        lane still belongs to it (evicted/cancelled jobs are dropped
        here — their blocks were already freed by `_evict_locked`).
        Returns ``(job, toks, start, n)`` or None."""
        while self._prefill_jobs:
            job = self._prefill_jobs[0]
            slot = self._slots[job.lane]
            if slot is None or slot.req is not job.req:
                self._prefill_jobs.popleft()
                self._note_chunk_queue_locked()
                continue
            start = job.next_pos
            n = min(self._chunk, job.P - start)
            toks = np.zeros((self._chunk,), np.int32)
            toks[:n] = job.prompt[start:start + n]
            return (job, toks, start, n)
        return None

    def _run_chunk(self, staged, hook):
        """Hand one staged prefill chunk to the device — OUTSIDE the
        lock (mirroring `_decode_step`), so submit()/cancel()/stats()
        never stall behind prefill compute (fault-hook injected sleeps
        included) — and return what `_commit_chunk` needs.  Nothing is
        fetched here: the program call returns once the chunk is
        queued."""
        prof = self._prof
        job, toks, start, n = staged
        # weight gather/requantize, timed apart from the device call so
        # a requantize after a weight swap shows up as its own cause
        with prof.phase("gather_params"):
            self._programs.gather_params()
        final = start + n >= job.P
        with prof.phase("prefill_chunk"):
            if hook is not None:
                hook("prefill")             # fault seam: once per chunk
            t0 = time.perf_counter()
            with prof.phase("dispatch"):
                first = self._programs.prefill_chunk(
                    job.row, toks, start, job.P, job.key, job.lane, n)
            # the chunk's stamp, in the ledger's record of the iteration
            # that handed it over: no sync
            prof.chunk(job.req.rid, start, n, time.monotonic())
        return job, start, n, final, first, t0

    def _commit_chunk(self, chunk) -> None:
        """Re-lock and commit a chunk `_run_chunk` handed over, with a
        slot identity check in case the request was evicted meanwhile;
        the FINAL chunk's commit fetches the first token (the one wait
        for the device here), delivers it and activates the lane."""
        prof = self._prof
        job, start, n, final, first, t0 = chunk
        req = job.req
        with prof.phase("prefill_chunk"):
            # only the final chunk's first-token pick is consumed —
            # don't force a host sync per intermediate chunk
            tok = int(np.asarray(first)) if final else None
            dt = time.perf_counter() - t0
        now = time.monotonic()
        with prof.phase("lock_wait"), self._work, prof.phase("commit"):
            job.t_work += dt
            slot = self._slots[job.lane]
            if slot is None or slot.req is not req:
                self._drop_job_locked(job)
                return                      # evicted while chunking
            job.next_pos = start + n
            if self._recurrent and start == 0:
                # the lane's state began from zero in this chunk
                self._state_resets += 1
                if telemetry.enabled():
                    telemetry.counter("serving_state_resets_total").inc()
            self._note_chunk_queue_locked()
            if not final:
                return
            self._drop_job_locked(job)
            # EWMA over the request's WHOLE prefill (all chunks):
            # the SLO shed estimate stays comparable to r12's
            self._prefill_ewma = job.t_work \
                if self._prefill_ewma is None \
                else 0.8 * self._prefill_ewma + 0.2 * job.t_work
            req.status = "running"
            req.trace.event("prefill", t=now,
                            dur_s=round(job.t_work, 6), token=tok,
                            cached_tokens=job.cached_len)
            req._deliver(tok, now)
            self._stats["admitted"] += 1
            # publish the prompt's full blocks into the prefix
            # cache now their content is final (COW: nothing
            # writes positions < P past this point)
            if not self._carried:
                self._pool.register(job.prompt, job.row)
            if telemetry.enabled():
                telemetry.counter("serving_admitted_total").inc()
                telemetry.histogram(
                    "serving_ttft_seconds",
                    labels={"path": self._path}) \
                    .observe(now - req.t_submit)
                telemetry.gauge("serving_kv_blocks_in_use") \
                    .set(self._pool.num_allocated)
            if tok == self._eos \
                    or len(req.tokens) >= req.max_new_tokens:
                self._retire_locked(job.lane)
                return
            self._tables[job.lane, :] = job.row
            self._toks[job.lane] = tok
            self._fresh[job.lane] = True    # the next step reads `_toks`
            self._pos[job.lane] = job.P
            self._end[job.lane] = job.P + req.max_new_tokens - 1
            self._active[job.lane] = True
            self._keys[job.lane, :] = job.key

    def _drop_job_locked(self, job: _PrefillJob) -> None:
        try:
            self._prefill_jobs.remove(job)
        except ValueError:
            pass
        self._note_chunk_queue_locked()

    def _pending_chunks_locked(self) -> int:
        """Chunks still to run across live prefill jobs (stale jobs —
        lane reassigned/evicted — excluded)."""
        ch = self._chunk
        return sum(-(-(j.P - j.next_pos) // ch)
                   for j in self._prefill_jobs
                   if (self._slots[j.lane] is not None
                       and self._slots[j.lane].req is j.req))

    def _note_chunk_queue_locked(self) -> None:
        if telemetry.enabled():
            telemetry.gauge("serving_prefill_chunk_queue_depth") \
                .set(self._pending_chunks_locked())

    def _pool_use_locked(self) -> dict:
        """The lanes' hold on the pool for the ledger's record: blocks
        reserved, and positions whose K/V the pool holds (`_pos` of a
        decoding lane, the step in flight's position with it, `next_pos`
        of one still prefilling), both summed over the occupied lanes; and
        how many lanes hold recurrent state.  With window layers, beside
        them the blocks of the lanes' rings that hold a visible position
        (the pool fields then speak of the full layers' pool alone)."""
        slots = self._slots
        # prefill jobs whose lane is still theirs
        jobs = [j for j in self._prefill_jobs
                if slots[j.lane] is not None and slots[j.lane].req is j.req]
        window = {}
        if self._window:
            # blocks of a lane's ring that hold a visible position: those
            # of the last `window - 1` written positions (the next query
            # sees them and itself); never more than the ring has
            bs, W1 = self._bs, self._window - 1
            wrote = np.concatenate([
                self._pos[self._active],
                np.fromiter((j.next_pos for j in jobs), np.int32,
                            len(jobs))])
            wrote = wrote[wrote > 0]
            window = {
                "window_blocks_held": int(np.sum(
                    (wrote - 1) // bs - np.maximum(wrote - W1, 0) // bs + 1)),
                "window_blocks_total":
                    self._B * self._programs.window_blocks}
        return {
            **window,
            "blocks_reserved": sum(len(s.blocks) for s in slots
                                   if s is not None),
            "blocks_total": self._num_blocks - 1,
            "block_size": self._bs,
            "positions_written": int(self._pos[self._active].sum())
            + sum(j.next_pos for j in jobs),
            # lanes holding live recurrent state: the decoding ones and
            # those whose first chunk has run
            "state_rows": (int(self._active.sum())
                           + sum(j.next_pos > 0 for j in jobs))
            if self._recurrent else 0}

    def _retire_locked(self, lane: int) -> None:
        req = self._slots[lane].req
        self._release_lane_locked(lane)
        req._finish("done")
        self._slo.note_done(req.ttft, req.tpot)
        self._slo.observe()
        self._stats["done"] += 1
        self._work.notify_all()             # drain()ers and submitters

    def _decode_step(self, snap, live, hook) -> None:
        """Hand one batched decode step over (when a lane is live), THEN
        read and commit the step handed over an iteration ago — device
        calls OUTSIDE the lock, so submit()/cancel() never block on
        compute (a fault hook's injected sleep included)."""
        prof = self._prof
        handed = None
        if live:
            with prof.phase("gather_params"):
                self._programs.gather_params()
            # the ledger's device_step cause includes the fault hook (an
            # injected stall IS device time to the requests waiting on
            # it) and the wait for the tokens in `_land_step`; the tpot
            # histogram keeps hand-over to tokens read
            with prof.phase("device_step"):
                if hook is not None:
                    hook("step")            # fault seam: counts as device
                t0 = time.perf_counter()
                with prof.phase("dispatch"):
                    nxt = self._programs.step(*snap, len(live))
            handed = (nxt, live, t0, int(self._flying is not None))
        self._land_step()
        self._flying = handed

    def _land_step(self) -> None:
        """Read the tokens of the step in flight, if there is one, and
        commit it: deliver, stamp, EOS test, retire.  A lane whose slot
        changed hands since the hand-over (evicted, cancelled, retired on
        an EOS the step before) is skipped: the token it ran for is
        discarded."""
        if self._flying is None:
            return
        (nxt, live, t0, ahead), self._flying = self._flying, None
        with self._prof.phase("device_step"):
            nxt = np.asarray(nxt)           # sync: tokens are consumed now
            dt = time.perf_counter() - t0
        counts = [int(c) for c in nxt[self._B:]]
        if self._moe is not None:
            # behind the lanes' tokens: pairs, tokens routed, busiest
            self._expert_counts, counts = tuple(counts[:3]), counts[3:]
        if self._index is not None:
            # then positions scored and attended, each in two words
            self._index_counts = wide_counts(counts)
        now = time.monotonic()

        def deliver(mark):
            for lane, req in live:
                slot = self._slots[lane]
                if slot is None or slot.req is not req:
                    continue                # evicted while stepping
                tok = int(nxt[lane])
                req._deliver(tok, now)
                self._toks[lane] = tok
                if mark:                    # every Nth step: cheap marks
                    req.trace.event("decode", t=now,
                                    pos=req.prompt.shape[0]
                                    + len(req.tokens) - 1,
                                    tokens=len(req.tokens),
                                    occupancy=len(live))
                if tok == self._eos \
                        or len(req.tokens) >= req.max_new_tokens:
                    self._retire_locked(lane)
            return dt

        self._commit_step(live, deliver, ahead)

    def _commit_step(self, live, deliver, ahead=0) -> None:
        """The commit `_land_step` and `_spec_step` share: re-lock,
        count the step (``ahead``: 1 where it was handed over while the
        step before was unread), let ``deliver(mark)`` hand the lanes their
        tokens (``mark``: this step leaves a trace mark; it returns the
        seconds a token took, for ``serving_tpot_seconds``), note
        occupancy, queue and the pool's use, then close the ledger's
        iteration."""
        prof = self._prof
        with prof.phase("lock_wait"), self._work, prof.phase("commit"):
            self._stats["steps"] += 1
            self._stats["steps_ahead"] += ahead
            step_no = self._stats["steps"]
            tpot = deliver(step_no % _TRACE_EVERY == 0)
            if telemetry.enabled():
                telemetry.histogram("serving_tpot_seconds",
                                    labels={"path": self._path}) \
                    .observe(tpot)
                telemetry.gauge("serving_batch_occupancy") \
                    .set(len(live))
            queue_depth = len(self._queue)
            pool_use = self._pool_use_locked()
            pool_use["state_resets"], self._state_resets = \
                self._state_resets, 0
            if self._moe is not None:
                (pool_use["expert_pairs"], pool_use["expert_tokens"],
                 pool_use["expert_busiest"]) = self._expert_counts
            if self._index is not None:
                (pool_use["index_positions_scored"],
                 pool_use["sparse_positions_attended"]) = self._index_counts
        # close the ledger OUTSIDE the engine lock (it takes its own
        # leaf lock + histogram locks; never nested under self._work)
        prof.end_step(rids=[req.rid for _, req in live],
                      occupancy=len(live), queue_depth=queue_depth,
                      step=step_no, ahead=ahead, **pool_use)
        if telemetry.enabled() and step_no % 8 == 0:
            # keep lock_witness_edges_total / lock_contention_seconds
            # scrapeable mid-run, not only after an end-of-run snapshot
            telemetry.profiler.snapshot_lock_witness()

    def _note_rollback_locked(self, reason: str) -> None:
        self._count(self._stats["spec_rollback"], reason)
        if telemetry.enabled():
            telemetry.counter("serving_spec_rollback_total",
                              labels={"reason": reason}).inc()

    def _spec_step(self, snap, live, hook) -> None:
        """One speculate-then-verify scheduler iteration — the
        speculative analogue of `_decode_step`, same
        snapshot → device-calls-outside-the-lock → re-lock-commit
        shape.  The draft program proposes k tokens per lane on its
        own pool; its outputs stay ON DEVICE and feed the verify
        program (no intermediate host sync); the verifier emits
        ``out[:, :accept_len+1]`` per lane.  Commit truncates each
        lane at eviction (slot-identity check), eos, and max_new —
        rollback is host-side position arithmetic only (see
        `programs._build_spec_verify` for why the device needs none).
        """
        prof = self._prof
        k = self._spec_k
        with prof.phase("gather_params"):
            self._programs.gather_params()
        t_h = time.perf_counter()
        with prof.phase("draft_step"):
            if hook is not None:
                hook("draft")               # fault seam: draft stream
            with prof.phase("dispatch"):
                d_toks, d_probs = self._programs.draft_step(
                    *snap, len(live))
        dt_draft = time.perf_counter() - t_h
        with prof.phase("verify_step"):
            if hook is not None:
                hook("step")                # fault seam: target stream
            t0 = time.perf_counter()
            with prof.phase("dispatch"):
                out, alen = self._programs.spec_verify(
                    *snap, d_toks, d_probs, len(live))
            out = np.asarray(out)           # sync: tokens consumed now
            alen = np.asarray(alen)
            dt = time.perf_counter() - t0
        now = time.monotonic()

        def deliver(mark):
            self._stats["spec_steps"] += 1
            proposed = accepted = delivered_total = 0
            for lane, req in live:
                slot = self._slots[lane]
                if slot is None or slot.req is not req:
                    continue                # evicted while speculating
                a = int(alen[lane])
                proposed += k
                accepted += a
                req.spec_proposed += k
                req.spec_accepted += a
                if a < k:
                    self._note_rollback_locked("rejected")
                delivered, stop = 0, None
                for j in range(a + 1):      # accepted run + correction/bonus
                    tok = int(out[lane, j])
                    req._deliver(tok, now)
                    delivered += 1
                    if tok == self._eos:
                        stop = "eos"
                        break
                    if len(req.tokens) >= req.max_new_tokens:
                        stop = "max_tokens"
                        break
                if stop is not None and delivered < a + 1:
                    self._note_rollback_locked(stop)
                self._pos[lane] += delivered
                self._toks[lane] = int(out[lane, delivered - 1])
                delivered_total += delivered
                if not BlockPool.covers(len(slot.blocks), self._bs,
                                        int(self._pos[lane]) - 1):
                    raise RuntimeError(
                        f"speculative commit outran lane {lane}'s "
                        f"reservation: pos {int(self._pos[lane])} vs "
                        f"{len(slot.blocks)} blocks of {self._bs}")
                if mark:                    # every Nth step: cheap marks
                    req.trace.event("decode", t=now,
                                    pos=int(self._pos[lane]),
                                    tokens=len(req.tokens),
                                    occupancy=len(live),
                                    spec_accepted=a)
                if telemetry.enabled():
                    telemetry.histogram("serving_spec_tokens_per_step",
                                        labels={"path": self._path}) \
                        .observe(delivered)
                if stop is not None \
                        or len(req.tokens) >= req.max_new_tokens:
                    self._retire_locked(lane)
            if proposed:
                rate = accepted / proposed
                self._stats["spec_proposed"] += proposed
                self._stats["spec_accepted"] += accepted
                ewma = self._stats["spec_ewma"]
                self._stats["spec_ewma"] = rate if ewma is None \
                    else 0.9 * ewma + 0.1 * rate
                if telemetry.enabled():
                    telemetry.gauge("serving_spec_accept_rate",
                                    labels={"engine": self._name}) \
                        .set(self._stats["spec_ewma"])
            # per-token time: the iteration's device time over the
            # mean tokens a lane actually got out of it
            return (dt + dt_draft) \
                / max(1.0, delivered_total / max(1, len(live)))

        self._commit_step(live, deliver)


def default_engine(net, **kw) -> ServingEngine:
    """The net's shared serving engine, built on first use and cached
    on the net (``net._serving_engine``).  Passing config kwargs that
    differ from the cached engine's closes it and builds a fresh one;
    equal (or no) kwargs reuse it — so `lm_stream` callers share one
    warm engine and one compiled program set."""
    eng = getattr(net, "_serving_engine", None)
    if eng is not None and not eng.closed:
        if not kw or kw == eng._ctor_kw:
            return eng
    if eng is not None and not eng.closed:
        try:
            eng.close()
        except ServingError:
            pass
    eng = ServingEngine(net, **kw)
    eng._ctor_kw = dict(kw)
    net._serving_engine = eng
    return eng
