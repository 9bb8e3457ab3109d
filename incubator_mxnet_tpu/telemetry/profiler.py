"""Unified timeline profiler + decode-stall attribution (ISSUE 17, 28).

Two halves, mirroring the reference MXNet's ``src/profiler/``
operator/phase-scoped timeline for this repo's serving stack:

**Per-iteration stall ledger.**  `EngineProfiler` is an always-on
host-side ledger the serving scheduler feeds: every scheduler-loop
phase runs inside ``with prof.phase(cause):``, which notes the phase's
own wall time under its cause AND holds a
``jax.profiler.TraceAnnotation("serving.<cause>", it=<step>)`` open, so
that under any ``jax.profiler`` trace the scheduler thread's phases
stand in the host plane on the clock of the device's operations.  At
each decode-step commit `end_step()` closes one `Iteration` record
decomposing the wall time since the previous commit (so prefill
interleave, lock waits and idle polls between steps are attributed,
not lost) into:

    device_step     decode step: fault hook + the blocking fetch of the
                    tokens of the step handed over an iteration before
    draft_step      speculative draft call (fault hook included)
    verify_step     speculative verify: fault hook + the blocking fetch
    prefill_chunk   prefill chunk: fault hook + the final chunk's fetch
    dispatch        host time inside the program calls until they
                    return (nested in the four causes above)
    gather_params   weight gather / requantize for the program call
    lock_wait       scheduler blocked acquiring the engine lock
    bookkeeping     reap + admission + chunk staging + lane snapshot
    commit          the locked section after a step or a chunk
    wait            idle condition-wait polls (no live lanes)
    gc              GC pauses on the scheduler thread (``gc.callbacks``)
    host_other      unattributed residue

The invariant is that the causes sum to the iteration's wall time: a
phase is charged its own time only (what phases nested in it took is
theirs), ``host_other`` is the exact remainder, and ``gc`` is carved
out of that remainder (a pause inside a timed phase is already inside
that phase's interval — carving keeps the sum exact instead of
double-counting).  Violations beyond tolerance are counted
(``invariant_violations``) and gated in ci/serving_smoke.py.

Records go into ONE process-wide bounded ring (`StepRing`, 32,768
records) that outlives the engines: `iterations(since, until)` cuts it
to a window of ``time.monotonic()`` — the clock of `Request.t_tokens` —
and says whether the ring still held the window's start.  A record also
carries the lanes' pool use (`blocks_reserved`, `positions_written`)
and a stamp for every prefill chunk handed over since the record before
it.  `recent_steps()`, the merged trace's scheduler lane and
``/profilez`` are served from the same ring.

Causes export as ``serving_step_stall_seconds{cause=}`` histograms when
telemetry is enabled; a hiccup detector flags steps slower than
k × rolling-p50 and records a full-detail stall record (per-cause
breakdown, co-resident rids, occupancy, queue depth) into a bounded
ring served by ``/stallz`` and bundled by the flight recorder.

**Merged capture.**  `capture(seconds)` (HTTP: ``/profilez?seconds=N``,
engine: ``ServingEngine.capture_profile()``) assembles ONE
chrome-trace/Perfetto JSON with named pid/tid lanes from the streams
that today export separately: requestlog lifecycle spans (one lane per
rid), tracer spans (per real thread), engine scheduler iterations (one
synthetic lane per engine: each iteration's causes laid end to end
inside its interval — their true places are in a ``jax.profiler``
trace), program timings from `telemetry.perf`, GC pauses and
lock-witness contention events — so a single trace shows a request's
admit→prefill→decode marks aligned against the engine loop that served
it.  All streams share the CLOCK_MONOTONIC family
(``time.perf_counter`` / ``time.monotonic`` on the platforms we run
on), so events interleave on one axis.  `validate_chrome_trace` is the
conformance checker both `tests/` and the CI smoke load traces with.

**Set-up record.**  A process's start by phase: `setup_phase(name)`
spans at the layer boundaries where a start does its work (gluon's
parameter initialisation, cast and hand-over, `ServingEngine` and
`PagedPrograms` construction, each program family's first call) and,
from a listener on ``jax.monitoring`` registered when this module is
imported, JAX's own ``compile.trace`` / ``compile.lower`` /
``compile.backend`` spans with the function's name and the persistent
cache's hits and misses, each a child of the compiling thread's open
phase.  All on ``time.monotonic()``, in ONE process-wide bounded record
that outlives the engines (`setup_spans()`); a phase also stands as
``setup.<name>`` in any running ``jax.profiler`` trace.

Knobs (environment):

* ``MXTPU_SERVING_PROFILER=0``   kill switch — ledger and set-up record
  record nothing, no span opens (the <5 µs/step disabled path the
  overhead test pins);
* ``MXTPU_PROFILER_HICCUP_K=K``  hiccup threshold multiplier over the
  rolling p50 (default 3.0);
* ``MXTPU_STALLZ_RING=N``        hiccup ring size (default 64).

THE NO-HOST-SYNC RULE applies: everything here reads host clocks,
already-host ints, or bounded deques — never device data.

Thread-safety: the ledger's accumulation state is touched only by the
scheduler thread (`phase`/`note`/`chunk`/`end_step`); published
aggregates (totals, hiccup ring) and the step ring are each guarded by
one leaf lock held only for copies — never while acquiring another
lock, so the runtime lock witness records no new ordering edges
through them.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import itertools
import json
import operator
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from jax import monitoring as _jax_monitoring
from jax.profiler import TraceAnnotation

from . import registry as _registry_mod

_tracing = TraceAnnotation.is_enabled      # is a jax.profiler trace running

__all__ = ["EngineProfiler", "Iteration", "StepRing", "iterations",
           "register", "unregister", "profilers",
           "stallz", "merged_chrome_trace", "capture",
           "validate_chrome_trace", "install_gc_hooks",
           "uninstall_gc_hooks", "gc_hooks_installed", "gc_events",
           "gc_pause_seconds", "snapshot_lock_witness",
           "SetupRecord", "setup_phase", "setup_phased",
           "record_setup_span", "setup_spans", "setup_spans_dropped",
           "DEFAULT_HICCUP_K", "DEFAULT_STALL_RING", "DEFAULT_STEP_RING",
           "DEFAULT_SETUP_SPANS", "CAUSES", "MAX_CAPTURE_S"]

DEFAULT_HICCUP_K = float(os.environ.get("MXTPU_PROFILER_HICCUP_K", "3.0")
                         or 3.0)
DEFAULT_STALL_RING = int(os.environ.get("MXTPU_STALLZ_RING", "64") or 64)
# iteration records the process keeps: a 54 s benchmark run at a 5 ms
# step is 11,000; about 800 bytes a record
DEFAULT_STEP_RING = 32768
# ledger causes (the serving_step_stall_seconds{cause=} label set, and
# the order of `Iteration.causes`); draft_step/verify_step are the
# speculative-decoding iteration's two device phases (ISSUE 19) — a
# speculative engine notes those instead of device_step
CAUSES = ("device_step", "draft_step", "verify_step", "prefill_chunk",
          "dispatch", "gather_params", "lock_wait", "bookkeeping",
          "commit", "wait", "gc", "host_other")
_INDEX = {c: i for i, c in enumerate(CAUSES)}
_LOCK_WAIT, _GC, _HOST_OTHER = (_INDEX[c] for c in
                                ("lock_wait", "gc", "host_other"))
# /profilez sleeps on an HTTP handler thread — bound it
MAX_CAPTURE_S = 30.0
# causes shorter than this get no slice in the merged trace's scheduler
# lane (a 2 µs lock_wait per iteration would drown it)
_EVENT_MIN_S = 20e-6
# steps a hiccup judgment needs in the rolling window before firing
_MIN_SAMPLES = 8
# and an absolute floor so microsecond jitter on an idle engine never
# "hiccups" (1 ms is far above any healthy CPU-smoke step residue)
_MIN_HICCUP_WALL_S = 1e-3


def _reg():
    from . import get_registry

    return get_registry()


def _snap_deque(dq: deque) -> list:
    """Copy a lock-free deque that other threads (or a GC callback
    firing inside THIS thread's allocations) may append to mid-copy —
    a bounded deque rotates on append, so plain iteration can raise
    ``deque mutated during iteration``.  Retry; an event ring a few
    appends newer is equally valid, losing the copy is not."""
    for _ in range(8):
        try:
            return list(dq)
        except RuntimeError:
            continue
    return []  # pragma: no cover — 8 consecutive mid-copy rotations


# --------------------------------------------------------------------- #
# GC pause accounting (gc.callbacks)
# --------------------------------------------------------------------- #
# The callback runs on whichever thread triggered the collection, so a
# per-thread cumulative lets the scheduler's ledger attribute exactly
# the pauses that interrupted IT.  Written only by the collecting
# thread under the GIL (per-tid key), read by anyone — no lock needed.
_gc_tls = threading.local()
_gc_events: deque = deque(maxlen=2048)      # {"t0","dur","gen","tid"}
_gc_by_thread: Dict[int, float] = {}
_gc_installed = False


def _gc_callback(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_tls.t0 = time.perf_counter()
        return
    t0 = getattr(_gc_tls, "t0", None)
    if t0 is None:
        return
    _gc_tls.t0 = None
    dur = time.perf_counter() - t0
    tid = threading.get_ident()
    _gc_by_thread[tid] = _gc_by_thread.get(tid, 0.0) + dur
    _gc_events.append({"t0": t0, "dur": dur,
                       "gen": int(info.get("generation", -1)), "tid": tid})


def install_gc_hooks() -> None:
    """Hook ``gc.callbacks`` (idempotent; cheap enough to stay on for
    the process lifetime — one clock read per collection phase)."""
    global _gc_installed
    if _gc_installed:
        return
    gc.callbacks.append(_gc_callback)
    _gc_installed = True


def uninstall_gc_hooks() -> None:
    global _gc_installed
    if not _gc_installed:
        return
    try:
        gc.callbacks.remove(_gc_callback)
    except ValueError:
        pass
    _gc_installed = False


def gc_hooks_installed() -> bool:
    return _gc_installed


def gc_pause_seconds(tid: Optional[int] = None) -> float:
    """Cumulative GC pause seconds observed on one thread (default: the
    calling thread) since the hooks were installed."""
    return _gc_by_thread.get(
        tid if tid is not None else threading.get_ident(), 0.0)


def gc_events(since: Optional[float] = None) -> List[dict]:
    """Recent GC pause events (perf_counter t0/dur seconds), oldest
    first, optionally only those ending at/after ``since``."""
    out = [dict(e) for e in _snap_deque(_gc_events)]
    if since is not None:
        out = [e for e in out if e["t0"] + e["dur"] >= since]
    return out


# --------------------------------------------------------------------- #
# the ring of iteration records (process-wide; outlives the engines)
# --------------------------------------------------------------------- #
class Iteration:
    """One scheduler iteration of one engine: the previous decode-step
    commit (``t0``) to this one (``t1``), both ``time.monotonic()``.

    ``causes`` holds seconds in the order of `CAUSES` and sums to
    ``t1 - t0``.  ``blocks_reserved`` and ``positions_written`` are sums
    over the occupied lanes at the commit (a block shared through the
    prefix cache counts once for every lane that holds it, in both, so
    ``positions_written <= blocks_reserved * block_size`` always);
    ``blocks_total`` is the pool's allocatable blocks, of
    ``block_size`` positions each.  ``chunks`` is
    ``(rid, start, n, t)`` for every prefill chunk handed over since the
    record before: ``n`` prompt tokens from position ``start``, ``t``
    the stamp taken when the chunk's program call RETURNED — the host
    had handed it over; only a prompt's final chunk is ever fetched.
    For a decoder with recurrent layers ``state_rows`` is the lanes that
    hold live recurrent state at the commit and ``state_resets`` the
    first chunks (a lane's state begun from zero) since the record
    before; both 0 otherwise.  ``ahead`` is 1 where the step this record
    commits was handed to the device while the step before it was still
    unread (the decode loop runs one step ahead of its reads), else 0.
    For a decoder with routed experts, counted on the device and read
    with the step's tokens: ``expert_pairs`` the token-expert pairs the
    experts held here computed in this iteration's step and chunk (all
    routed layers), ``expert_tokens`` the tokens that went through a
    router (a routed layer each), ``expert_busiest`` the most pairs one
    held expert of one layer took in one program.  For one with window
    layers ``window_blocks_held`` is the blocks of the lanes' rings that
    hold a visible position at the commit, of ``window_blocks_total``;
    the three pool fields above then speak of the full layers' pool.  All
    five 0 otherwise.  For a decoder whose attention layers select what
    they attend by an index, counted on the device likewise:
    ``index_positions_scored`` the positions the index scored, summed over
    the iteration's queries (a decode lane, a chunk's token) and indexed
    layers, and ``sparse_positions_attended`` the same sum of the
    positions then attended, min(context, topk).
    """

    __slots__ = ("engine", "step", "t0", "t1", "causes", "occupancy",
                 "queue_depth", "blocks_reserved", "blocks_total",
                 "block_size", "positions_written", "chunks",
                 "state_rows", "state_resets", "ahead", "expert_pairs",
                 "expert_tokens", "expert_busiest", "window_blocks_held",
                 "window_blocks_total", "index_positions_scored",
                 "sparse_positions_attended")

    def __init__(self, engine, step, t0, t1, causes, occupancy=0,
                 queue_depth=0, blocks_reserved=0, blocks_total=0,
                 block_size=0, positions_written=0, chunks=(),
                 state_rows=0, state_resets=0, ahead=0, expert_pairs=0,
                 expert_tokens=0, expert_busiest=0, window_blocks_held=0,
                 window_blocks_total=0, index_positions_scored=0,
                 sparse_positions_attended=0):
        self.engine = engine
        self.step = step
        self.t0 = t0
        self.t1 = t1
        self.causes = causes
        self.occupancy = occupancy
        self.queue_depth = queue_depth
        self.blocks_reserved = blocks_reserved
        self.blocks_total = blocks_total
        self.block_size = block_size
        self.positions_written = positions_written
        self.chunks = chunks
        self.state_rows = state_rows
        self.state_resets = state_resets
        self.ahead = ahead
        self.expert_pairs = expert_pairs
        self.expert_tokens = expert_tokens
        self.expert_busiest = expert_busiest
        self.window_blocks_held = window_blocks_held
        self.window_blocks_total = window_blocks_total
        self.index_positions_scored = index_positions_scored
        self.sparse_positions_attended = sparse_positions_attended

    def as_dict(self) -> dict:
        d = {k: getattr(self, k) for k in self.__slots__}
        d["causes"] = dict(zip(CAUSES, self.causes))
        d["chunks"] = [list(c) for c in self.chunks]
        d["wall_s"] = self.t1 - self.t0
        return d


class StepRing:
    """Bounded ring of `Iteration` records, oldest dropped first.  One
    per process (`iterations()` reads it); tests make their own."""

    def __init__(self, cap: int = DEFAULT_STEP_RING):
        self._ring: deque = deque(maxlen=max(1, int(cap)))
        self._lock = threading.Lock()       # leaf: append and copy only

    @property
    def cap(self) -> int:
        return self._ring.maxlen

    def push(self, rec: Iteration) -> None:
        with self._lock:
            self._ring.append(rec)

    def window(self, since: Optional[float] = None,
               until: Optional[float] = None,
               engine: Optional[str] = None
               ) -> Tuple[List[Iteration], bool]:
        """``(records, held)``: the records (of one engine, or of all)
        whose ``t1`` lies in ``[since, until)``, oldest first, and
        whether the ring still held ``since`` — False once it has
        dropped records and its oldest one began after ``since`` (or
        when it is empty): the window's start may be missing then."""
        with self._lock:
            recs = list(self._ring)
        held = bool(recs) and (len(recs) < self.cap or (
            since is not None and recs[0].t0 <= since))
        return [r for r in recs
                if (engine is None or r.engine == engine)
                and (since is None or r.t1 >= since)
                and (until is None or r.t1 < until)], held


_steps = StepRing()


def iterations(since: Optional[float] = None,
               until: Optional[float] = None,
               engine: Optional[str] = None
               ) -> Tuple[List[Iteration], bool]:
    """`StepRing.window` of the process's ring: every engine's
    iterations, still there after ``engine.close()``."""
    return _steps.window(since, until, engine)


# --------------------------------------------------------------------- #
# the set-up record: a process's start by phase (process-wide)
# --------------------------------------------------------------------- #
# spans a record keeps: a start of a 3B-parameter decoder records some
# 8,200 (every eager operation's trace is one), four times that is room
DEFAULT_SETUP_SPANS = 32768
# JAX's compile events -> the record's span names
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend"}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}


def _setup_on() -> bool:
    return os.environ.get("MXTPU_SERVING_PROFILER", "1") != "0"


class SetupRecord:
    """The spans of a process's start, in the order they closed: the
    first ``cap`` of them (a start is what the record is for; spans
    beyond it are only counted, `dropped`).  A span is a dict: ``name``,
    ``t0``, ``t1`` (``time.monotonic()``), ``tid`` and ``thread`` (its
    name), ``id`` and ``parent`` (the id of the span open in that thread
    when it opened; None at a thread's top), and what its site adds:
    ``fun_name``, ``hits`` and ``misses`` on JAX's compile spans,
    ``bytes_in_use`` and ``peak_bytes_in_use`` of the first local device
    where a top-level phase closed with the backend up.  One per process
    (`setup_spans()` reads it); tests make their own."""

    def __init__(self, cap: int = DEFAULT_SETUP_SPANS):
        self.cap = max(1, int(cap))
        self.dropped = 0
        self._spans: List[dict] = []
        self._lock = threading.Lock()       # leaf: append and copy only

    def add(self, span: dict) -> None:
        with self._lock:
            if len(self._spans) < self.cap:
                self._spans.append(span)
            else:
                self.dropped += 1

    def spans(self) -> List[dict]:
        with self._lock:
            return [dict(s) for s in self._spans]


_setup = SetupRecord()
_setup_ids = itertools.count(1)
# per thread: `.open`, the [(id, name)] of its open phases, innermost
# last; `.hits` / `.misses`, cache events since its last backend span
_setup_tls = threading.local()


def _open_phases() -> list:
    try:
        return _setup_tls.open
    except AttributeError:
        _setup_tls.open = stack = []
        return stack


def _device_memory() -> dict:
    """The first local device's bytes in use and peak, where the backend
    is up (a phase must not start it) and reports them; else nothing.
    A host call: no device sync."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return {}
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def _close_span(name: str, t0: float, t1: float, span_id: int,
                parent: Optional[int], attrs: dict) -> None:
    th = threading.current_thread()
    span = dict(attrs, name=name, t0=t0, t1=t1, id=span_id, parent=parent,
                tid=th.ident, thread=th.name)
    if parent is None and not name.startswith("compile."):
        span.update(_device_memory())
    _setup.add(span)


class _SetupPhase:
    """One `setup_phase` block: a span of the set-up record, and
    ``setup.<name>`` in a running ``jax.profiler`` trace."""

    __slots__ = ("_name", "_attrs", "_id", "_parent", "_t0", "_span")

    def __init__(self, name: str, attrs: dict):
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        stack = _open_phases()
        self._parent = stack[-1][0] if stack else None
        self._id = next(_setup_ids)
        stack.append((self._id, self._name))
        self._span = TraceAnnotation("setup." + self._name,
                                     **self._attrs).__enter__() \
            if _tracing() else None
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self._span is not None:
            self._span.__exit__(None, None, None)
        _open_phases().pop()
        _close_span(self._name, self._t0, t1, self._id, self._parent,
                    self._attrs)
        return False


def setup_phase(name: str, **attrs):
    """``with setup_phase(name):`` — record the block as one span of the
    process's set-up record (`SetupRecord`) and as ``setup.<name>`` in
    any running ``jax.profiler`` trace.  A phase does not nest in itself:
    inside an open phase of the same name (a block's cast casting its
    children) the block is that phase's, and nothing opens.  A span's
    self time is its duration less what its children (phases and JAX's
    compile spans) cover.  Nothing when ``MXTPU_SERVING_PROFILER=0``."""
    if not _setup_on() or any(n == name for _, n in _open_phases()):
        return _NO_PHASE
    return _SetupPhase(name, attrs)


def setup_phased(name: str):
    """Decorator: the whole call is ``setup_phase(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def timed(*args, **kwargs):
            with setup_phase(name):
                return fn(*args, **kwargs)
        return timed
    return wrap


def record_setup_span(name: str, t0: float, t1: float) -> None:
    """Put a phase timed without `setup_phase` (the package's own import,
    which precedes this module) into the set-up record, at the calling
    thread's top."""
    if _setup_on():
        _close_span(name, t0, t1, next(_setup_ids), None, {})


def setup_spans() -> List[dict]:
    """The process's set-up record (`SetupRecord`), oldest close first;
    still there after the engines close."""
    return _setup.spans()


def setup_spans_dropped() -> int:
    """Spans the process's set-up record did not keep, being full."""
    return _setup.dropped


def _on_compile_span(event: str, start: float, end: float, **kw) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is None or not _setup_on():
        return
    stack = _open_phases()
    attrs = {"fun_name": str(kw.get("fun_name", ""))}
    if name == "compile.backend":
        attrs["hits"] = getattr(_setup_tls, "hits", 0)
        attrs["misses"] = getattr(_setup_tls, "misses", 0)
        _setup_tls.hits = _setup_tls.misses = 0
    _close_span(name, start + _WALL_TO_MONOTONIC, end + _WALL_TO_MONOTONIC,
                next(_setup_ids), stack[-1][0] if stack else None, attrs)


def _on_cache_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None and _setup_on():
        setattr(_setup_tls, key, getattr(_setup_tls, key, 0) + 1)


# JAX stamps its compile spans with time.time(): one offset, taken here,
# puts them on the record's clock
_WALL_TO_MONOTONIC = time.monotonic() - time.time()
_jax_monitoring.register_event_time_span_listener(_on_compile_span)
_jax_monitoring.register_event_listener(_on_cache_event)


# --------------------------------------------------------------------- #
# per-engine stall ledger
# --------------------------------------------------------------------- #
class _Phase:
    """One cause's reusable ``with`` block (scheduler thread only; a
    cause never nests in itself).  Notes its own time — what phases
    nested in it took is theirs — and holds the profiler-clock span
    open.  A phase that starts inside an open ``lock_wait`` ends it:
    ``with prof.phase("lock_wait"), lock, prof.phase("commit"):`` reads
    as it runs."""

    __slots__ = ("_prof", "_idx", "_name", "_span", "_t0", "_inner",
                 "_parent")

    def __init__(self, prof: "EngineProfiler", cause: str):
        self._prof = prof
        self._idx = _INDEX[cause]
        self._name = "serving." + cause
        self._t0 = None

    def __enter__(self):
        p = self._prof
        par = p._open
        if par is not None and par._idx == _LOCK_WAIT:
            par.__exit__()
            par = p._open
        self._parent = par
        p._open = self
        self._inner = 0.0
        # jax's own check (44 ns), the one a TraceMe makes of itself:
        # building the span costs 0.5 us, which only a running trace
        # gets anything for
        self._span = TraceAnnotation(self._name, it=p._it).__enter__() \
            if _tracing() else None
        self._t0 = p._clock()
        return self

    def __exit__(self, *exc):
        t0 = self._t0
        if t0 is None:                      # ended by the phase it led to
            return False
        p = self._prof
        dur = p._clock() - t0
        self._t0 = None
        if self._span is not None:
            self._span.__exit__(None, None, None)
        p._open = par = self._parent
        if par is not None:
            par._inner += dur
        p._acc[self._idx] += dur - self._inner
        return False


_NO_PHASE = contextlib.nullcontext()      # what phase() is with the ledger off


class EngineProfiler:
    """Per-iteration stall-attribution ledger for one engine.

    The scheduler thread is the only caller of `phase()`/`note()`/
    `chunk()`/`end_step()` (accumulation needs no lock); HTTP/flight
    readers go through `stallz()`/`stall_table()`/`recent_steps()`,
    which copy under leaf locks.  ``clock`` (default
    ``time.monotonic``, the clock of the requests' token stamps),
    ``gc_seconds`` and ``steps`` (the ring records go to; default the
    process's) are injectable for the tests.
    """

    def __init__(self, name: str, *, hiccup_k: Optional[float] = None,
                 ring: Optional[int] = None, window: int = 128,
                 clock: Callable[[], float] = time.monotonic,
                 gc_seconds: Optional[Callable[[], float]] = None,
                 enabled: Optional[bool] = None,
                 steps: Optional[StepRing] = None):
        self.name = name
        self._clock = clock
        self._gc_seconds = gc_seconds if gc_seconds is not None \
            else gc_pause_seconds
        self._enabled = bool(enabled) if enabled is not None else \
            os.environ.get("MXTPU_SERVING_PROFILER", "1") != "0"
        self.hiccup_k = float(hiccup_k if hiccup_k is not None
                              else DEFAULT_HICCUP_K)
        self._ring = steps if steps is not None else _steps
        # scheduler thread only: the iteration in progress (seconds
        # per cause, in the order of CAUSES)
        self._acc = [0.0] * len(CAUSES)
        self._chunks: List[tuple] = []
        self._phases = {c: _Phase(self, c) for c in CAUSES}
        self._open: Optional[_Phase] = None
        self._it = 1                         # the step it will commit as
        self._step_t0 = self._clock()
        self._last_gc = self._gc_seconds()
        self._walls: deque = deque(maxlen=max(8, int(window)))
        self._p50: Optional[float] = None
        self._p50_at = 0
        self.steps = 0
        self.hiccups_total = 0
        self.invariant_violations = 0
        # published aggregates: copies only under this leaf lock, never
        # another lock while holding it (lock-witness discipline)
        self._pub = threading.Lock()
        self._totals = (0.0,) * len(CAUSES)  # replaced whole: no lock
        self._total_wall = 0.0
        self._hiccups: deque = deque(
            maxlen=max(1, int(ring if ring is not None
                              else DEFAULT_STALL_RING)))

    # -- hot path (scheduler thread) ----------------------------------- #
    @property
    def enabled(self) -> bool:
        return self._enabled

    def set_enabled(self, on: bool) -> None:
        """Runtime kill switch (the enabled-vs-disabled CI A/B seam).
        Re-anchors the step window so a toggle never attributes the
        disabled era to the next step."""
        on = bool(on)
        if on and not self._enabled:
            self._acc = [0.0] * len(CAUSES)
            self._chunks = []
            self._step_t0 = self._clock()
            self._last_gc = self._gc_seconds()
        self._enabled = on

    def phase(self, cause: str):
        """``with prof.phase(cause):`` — time the block under ``cause``
        and show it as the span ``serving.<cause>`` (argument ``it``:
        the step this iteration will commit as) in any running
        ``jax.profiler`` trace.  One flag read when the ledger is off."""
        return self._phases[cause] if self._enabled else _NO_PHASE

    def note(self, cause: str, dur: float) -> None:
        """Accumulate ``dur`` seconds under ``cause`` for the iteration
        in progress.  One list update when enabled; one flag read when
        not (the <5 µs disabled-path budget)."""
        if self._enabled:
            self._acc[_INDEX[cause]] += dur

    def chunk(self, rid: int, start: int, n: int, t: float) -> None:
        """Stamp one prefill chunk handed over (see `Iteration.chunks`)."""
        if self._enabled:
            self._chunks.append((rid, start, n, t))

    def end_step(self, *, rids=(), occupancy: int = 0,
                 queue_depth: int = 0, step: int = 0,
                 blocks_reserved: int = 0, blocks_total: int = 0,
                 block_size: int = 0,
                 positions_written: int = 0, state_rows: int = 0,
                 state_resets: int = 0, ahead: int = 0,
                 expert_pairs: int = 0, expert_tokens: int = 0,
                 expert_busiest: int = 0, window_blocks_held: int = 0,
                 window_blocks_total: int = 0,
                 index_positions_scored: int = 0,
                 sparse_positions_attended: int = 0) -> Optional[dict]:
        """Close the iteration at a decode-step commit: compute the
        wall since the previous commit, carve gc + residue, push the
        record, feed histograms, judge the hiccup threshold.  Returns
        the stall record when the step was flagged, else None."""
        if not self._enabled:
            return None
        now = self._clock()
        t0, self._step_t0 = self._step_t0, now
        wall = now - t0
        acc, self._acc = self._acc, [0.0] * len(CAUSES)
        chunks, self._chunks = self._chunks, []
        self._it = step + 1
        residue = wall - sum(acc)
        cur_gc = self._gc_seconds()
        gc_dt = cur_gc - self._last_gc
        self._last_gc = cur_gc
        # a pause inside a timed phase already sits in that phase's
        # interval; only the part that fell in unattributed time can be
        # carved without breaking the sum-to-wall invariant
        gc_cause = min(gc_dt, residue) if gc_dt > 0 and residue > 0 else 0.0
        acc[_GC] += gc_cause
        acc[_HOST_OTHER] = max(0.0, residue - gc_cause)
        self.steps += 1
        # the sum is the wall by construction unless phases were
        # charged more than the wall (overlapping notes)
        if -residue > 0.05 * wall + 1e-6:
            self.invariant_violations += 1
        rec = Iteration(self.name, step, t0, now, tuple(acc), occupancy,
                        queue_depth, blocks_reserved, blocks_total,
                        block_size, positions_written, tuple(chunks),
                        state_rows, state_resets, ahead, expert_pairs,
                        expert_tokens, expert_busiest, window_blocks_held,
                        window_blocks_total, index_positions_scored,
                        sparse_positions_attended)
        self._ring.push(rec)
        self._totals = tuple(map(operator.add, self._totals, acc))
        self._total_wall += wall
        if _registry_mod._enabled:
            reg = _reg()
            for cause, s in zip(CAUSES, acc):
                if s or cause in ("gc", "host_other"):
                    reg.histogram("serving_step_stall_seconds",
                                  {"cause": cause}).observe(s)
        # rolling p50 over the wall window, recomputed every 16 steps
        # (every step while the window is still small)
        walls = self._walls
        walls.append(wall)
        n = len(walls)
        if self._p50 is None or n < 16 \
                or self.steps - self._p50_at >= 16:
            self._p50 = sorted(walls)[n // 2]
            self._p50_at = self.steps
        p50 = self._p50
        if not (n >= _MIN_SAMPLES and p50 is not None and p50 > 0
                and wall > self.hiccup_k * p50
                and wall > _MIN_HICCUP_WALL_S):
            return None
        hic = dict(rec.as_dict(), dominant=CAUSES[acc.index(max(acc))],
                   p50_s=round(p50, 6), ratio=round(wall / p50, 2),
                   rids=[int(r) for r in rids])
        self.hiccups_total += 1
        if _registry_mod._enabled:
            _reg().counter("serving_step_hiccups_total",
                           {"engine": self.name}).inc()
        with self._pub:
            self._hiccups.append(hic)
        return hic

    # -- readers (any thread) ------------------------------------------ #
    def stall_table(self) -> List[dict]:
        """Aggregate attribution rows, biggest cause first:
        ``{"cause", "total_s", "share", "per_step_ms"}``."""
        totals, wall = self._totals, self._total_wall
        steps = max(1, self.steps)
        rows = [{"cause": c, "total_s": round(s, 6),
                 "share": round(s / wall, 4) if wall > 0 else 0.0,
                 "per_step_ms": round(s / steps * 1e3, 4)}
                for c, s in zip(CAUSES, totals) if s]
        rows.sort(key=lambda r: -r["total_s"])
        return rows

    def recent_stalls(self, n: Optional[int] = None) -> List[dict]:
        """Recent hiccup records, oldest first (all by default)."""
        with self._pub:
            out = [dict(h) for h in self._hiccups]
        return out if n is None else out[-int(n):]

    def recent_steps(self, n: Optional[int] = None) -> List[dict]:
        """This engine's iteration records still in the ring, as
        dicts, oldest first (all by default)."""
        recs = self._ring.window(engine=self.name)[0]
        return [r.as_dict() for r in (recs if n is None
                                      else recs[-int(n):])]

    def stallz(self) -> dict:
        """The per-engine ``/stallz`` payload: config, invariant
        health, the aggregate cause table, and the worst recent
        hiccups (slowest first)."""
        with self._pub:
            hiccups = [dict(h) for h in self._hiccups]
            ring_cap = self._hiccups.maxlen
        hiccups.sort(key=lambda h: -h["wall_s"])
        return {"engine": self.name, "enabled": self._enabled,
                "hiccup_k": self.hiccup_k, "steps": self.steps,
                "rolling_p50_s": None if self._p50 is None
                else round(self._p50, 6),
                "invariant_violations": self.invariant_violations,
                "hiccups_total": self.hiccups_total,
                "ring_cap": ring_cap,
                "attribution": self.stall_table(),
                "hiccups": hiccups}

    def chrome_events(self, since: Optional[float] = None) -> List[tuple]:
        """Slices ``(name, cat, t0, dur)`` for the merged trace's
        scheduler lane, from the ring: each iteration's causes laid end
        to end from its ``t0`` in `CAUSES` order (durations exact,
        places not), and the flagged iterations as ``hiccup`` slices;
        optionally only iterations ending at/after ``since``."""
        out = []
        for r in self._ring.window(since, engine=self.name)[0]:
            t = r.t0
            for cause, s in zip(CAUSES, r.causes):
                if s >= _EVENT_MIN_S:
                    out.append((cause, "scheduler", t, s))
                t += s
        out += [("hiccup", "stall", h["t0"], h["wall_s"])
                for h in self.recent_stalls()
                if since is None or h["t1"] >= since]
        return out


# --------------------------------------------------------------------- #
# process-wide profiler registry (engines register at construction)
# --------------------------------------------------------------------- #
_profilers: Dict[str, EngineProfiler] = {}


def register(prof: EngineProfiler) -> EngineProfiler:
    _profilers[prof.name] = prof
    return prof


def unregister(name: str) -> None:
    _profilers.pop(name, None)


def profilers() -> Dict[str, EngineProfiler]:
    return dict(_profilers)


def stallz() -> dict:
    """The ``/stallz`` payload across every registered engine."""
    return {"engines": {name: p.stallz()
                        for name, p in sorted(_profilers.items())}}


def snapshot_lock_witness() -> bool:
    """Export the runtime lock witness's aggregates to the telemetry
    gauges if (and only if) the witness is installed — the periodic
    hook the engine rides so ``lock_witness_edges_total`` /
    ``lock_contention_seconds`` are scrapeable mid-run, not only after
    the end-of-run `assert_clean()`."""
    try:
        from .. import lock_witness
    except Exception:  # pragma: no cover — package always has it
        return False
    if not lock_witness.installed():
        return False
    lock_witness.snapshot()
    return True


# --------------------------------------------------------------------- #
# merged chrome-trace capture
# --------------------------------------------------------------------- #
# synthetic tid lanes (request lanes use the rid, real threads their
# ident — keep these far above both ranges and stable across captures)
_TID_SCHED_BASE = 900000
_TID_PROGRAMS = 990001
_TID_LOCKS = 990002


def _meta(pid: int, tid, name: str, sort: int) -> List[dict]:
    return [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": name}},
            {"name": "thread_sort_index", "ph": "M", "pid": pid,
             "tid": tid, "args": {"sort_index": sort}}]


def merged_chrome_trace(since: Optional[float] = None) -> dict:
    """ONE chrome-trace dict merging every timeline source in the
    process (see module docstring), with ``thread_name`` metadata
    naming each lane.  ``since`` (perf_counter seconds) keeps only
    events still in flight at or after that instant."""
    pid = os.getpid()
    events: List[dict] = []
    meta: List[dict] = [{"name": "process_name", "ph": "M", "pid": pid,
                         "tid": 0, "args": {"name": "mxtpu"}}]
    cut = None if since is None else since * 1e6

    def keep(ev: dict) -> bool:
        return cut is None or ev["ts"] + ev.get("dur", 0.0) >= cut

    # 1. requestlog lifecycle spans: one lane per rid (already rendered
    #    by requestlog.chrome_trace — monotonic clock, same family)
    from . import requestlog

    rids = set()
    for ev in requestlog.chrome_trace()["traceEvents"]:
        if keep(ev):
            events.append(ev)
            rids.add(ev["tid"])
    for rid in sorted(rids):
        meta += _meta(pid, rid, f"request rid={rid}", 100 + rid)

    # 2. tracer spans: real thread lanes
    from . import tracer as _tracer

    tids = set()
    for s in _tracer.spans():
        ev = {"name": s.name, "cat": "telemetry", "ph": "X",
              "ts": s.t0 * 1e6, "dur": s.dur * 1e6, "pid": pid,
              "tid": s.tid, "args": {"step": s.step, "depth": s.depth}}
        if keep(ev):
            events.append(ev)
            tids.add(s.tid)

    # 3. engine scheduler phases: one synthetic lane per engine
    for i, (name, prof) in enumerate(sorted(_profilers.items())):
        tid = _TID_SCHED_BASE + i
        meta += _meta(pid, tid, f"{name} scheduler", 10 + i)
        for pname, cat, t0, dur in prof.chrome_events(since=since):
            events.append({"name": pname, "cat": cat, "ph": "X",
                           "ts": t0 * 1e6, "dur": dur * 1e6,
                           "pid": pid, "tid": tid,
                           "args": {"engine": name}})

    # 4. program timings (telemetry.perf note_timing stream)
    from . import perf as _perf

    prog_evs = _perf.recent_timings(since=since)
    if prog_evs:
        meta += _meta(pid, _TID_PROGRAMS, "programs", 50)
        for e in prog_evs:
            events.append({"name": e["program"], "cat": "program",
                           "ph": "X", "ts": e["t0"] * 1e6,
                           "dur": e["dur"] * 1e6, "pid": pid,
                           "tid": _TID_PROGRAMS, "args": {}})

    # 5. GC pauses: on their real thread lanes (they interrupt it)
    for e in gc_events(since=since):
        events.append({"name": f"gc(gen{e['gen']})", "cat": "gc",
                       "ph": "X", "ts": e["t0"] * 1e6,
                       "dur": e["dur"] * 1e6, "pid": pid,
                       "tid": e["tid"], "args": {}})
        tids.add(e["tid"])

    # 6. lock-witness contention events (only when installed)
    try:
        from .. import lock_witness

        cont = lock_witness.recent_contention(since=since) \
            if lock_witness.installed() else []
    except Exception:
        cont = []
    if cont:
        meta += _meta(pid, _TID_LOCKS, "lock contention", 60)
        for e in cont:
            events.append({"name": e["site"], "cat": "lock", "ph": "X",
                           "ts": e["t0"] * 1e6, "dur": e["dur"] * 1e6,
                           "pid": pid, "tid": _TID_LOCKS, "args": {}})

    for tid in sorted(tids):
        meta += _meta(pid, tid, f"thread {tid}", 200)
    events.sort(key=lambda e: e["ts"])
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def capture(seconds: float = 1.0) -> dict:
    """On-demand merged capture: let ``seconds`` of activity accumulate
    (bounded by ``MAX_CAPTURE_S``; 0 = everything still buffered), then
    assemble the merged trace for that window."""
    s = max(0.0, min(float(seconds), MAX_CAPTURE_S))
    if s <= 0.0:
        return merged_chrome_trace()
    t0 = time.perf_counter()
    time.sleep(s)
    return merged_chrome_trace(since=t0)


# --------------------------------------------------------------------- #
# trace conformance validator (shared by tests and the CI smoke)
# --------------------------------------------------------------------- #
_KNOWN_PH = frozenset("XiIMBEC")


def validate_chrome_trace(trace) -> List[str]:
    """Conformance-check one chrome-trace dict (or its JSON string).
    Returns human-readable problems; an empty list means the trace
    loads in chrome://tracing / Perfetto:

    * top level is ``{"traceEvents": [...]}``;
    * every event has ``name``/``ph``/``pid``/``tid`` (+ numeric
      ``ts`` for non-metadata events);
    * ``X`` slices carry a numeric ``dur >= 0``;
    * non-metadata events are emitted in non-decreasing ``ts`` order
      (the lane/ts-monotonicity contract the tests pin).
    """
    problems: List[str] = []
    if isinstance(trace, (str, bytes)):
        try:
            trace = json.loads(trace)
        except ValueError as e:
            return [f"not JSON: {e}"]
    if not isinstance(trace, dict) or \
            not isinstance(trace.get("traceEvents"), list):
        return ["top level is not {'traceEvents': [...]}"]
    last_ts = None
    for i, ev in enumerate(trace["traceEvents"]):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _KNOWN_PH:
            problems.append(f"event {i}: unknown ph {ph!r}")
            continue
        for k in ("name", "pid", "tid"):
            if k not in ev:
                problems.append(f"event {i} ({ev.get('name')!r}): "
                                f"missing {k!r}")
        if ph == "M":
            continue                      # metadata events carry no ts
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            problems.append(f"event {i} ({ev.get('name')!r}): "
                            f"non-numeric ts {ts!r}")
            continue
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"event {i} ({ev.get('name')!r}): "
                                f"X slice with bad dur {dur!r}")
        if last_ts is not None and ts < last_ts:
            problems.append(f"event {i} ({ev.get('name')!r}): ts goes "
                            f"backwards ({ts} < {last_ts})")
        last_ts = ts
    return problems
