"""Runtime guard against recompilation storms.

JAX silently retraces a jitted callable whenever it sees a new
combination of input shapes/dtypes or static-argument values.  On TPU a
single compile costs seconds; a training loop that perturbs shapes every
step (python-int batch sizes, growing pad lengths, fresh closures per
iteration) turns into a compile-bound crawl without any error.  This
module makes that failure loud.

:class:`RetraceGuard` counts compilations per callable *name* while
active and raises :class:`RetraceError` when any watched name exceeds
its budget.  Counting hooks into JAX's compile logging (the
``jax._src.interpreters.pxla`` logger emits ``"Compiling <name> with
global shapes and types ..."`` at DEBUG for every cache miss), so no JAX
internals are monkeypatched and jitted code runs unmodified.

Names are the only identity the log line carries, so counting is coarse:
two different closures both called ``raw_fn`` share one counter.  Budget
accordingly (one compile per distinct shape signature per callable is
legitimate) or pass ``watch=`` to restrict counting to the program names
you care about.

Usage::

    with RetraceGuard(budget=8, watch={"train_step"}) as guard:
        for batch in loader:
            train_step(params, batch)
    # raises RetraceError on exit if train_step compiled > 8 times

The test suite activates a guard around every test via an autouse
fixture in ``tests/conftest.py`` (budget ``MXTPU_RETRACE_BUDGET``,
opt-out ``MXTPU_RETRACE_GUARD=0``).
"""
from __future__ import annotations

import logging
import os
import threading
from collections import Counter
from typing import Dict, Iterable, Optional, Set

from .base import MXNetError

__all__ = ["RetraceError", "RetraceGuard", "DEFAULT_BUDGET", "PROGRAM_NAMES",
           "subscribe_compiles", "unsubscribe_compiles",
           "install_telemetry_feed", "remove_telemetry_feed"]

# Loggers that announce a compilation.  pxla carries the callable name in
# args[0]; dispatch only carries elapsed times, so pxla is the one we tap.
_COMPILE_LOGGER = "jax._src.interpreters.pxla"
_COMPILE_MSG_PREFIX = "Compiling "

DEFAULT_BUDGET = int(os.environ.get("MXTPU_RETRACE_BUDGET", "64"))

# The package's jitted program entry points (gluon/block.py _program_jits
# and the Trainer fused steps).  The conftest guard watches only these:
# jax-internal primitive jits (broadcast_in_dim, convert_element_type,
# ...) legitimately compile once per shape and would swamp a global count.
PROGRAM_NAMES: Set[str] = {
    "raw_fn", "grad_fn", "fwd_record_fn",       # hybridized block programs
    "chain", "chain_unrolled",                  # fused optimizer chains
    "stacked_with_sync", "full",                # fused train steps
    "full_zero",                                # ZeRO-1 explicit-tier step:
                                                # toggling zero_stage swaps
                                                # programs and legitimately
                                                # compiles this once
    "_flash_core",                              # flash-attention kernel jit
    "_paged_core", "_paged_core_q8",            # paged-attention kernel jits
                                                # (direct calls outside the
                                                # step program, e.g. tests)
    "serving_step", "serving_prefill_chunk",    # continuous-batching decode:
                                                # ONE step program + ONE
                                                # fixed-width prefill-chunk
                                                # program per engine (no
                                                # pow2 bucket ladder)
    "serving_step_kv8",                         # the int8-KV-pool program
    "serving_prefill_chunk_kv8",                # family (kv_dtype="int8")
    "serving_draft_step",                       # speculative decoding
    "serving_draft_prefill_chunk",              # (ISSUE 19): draft k-step
    "serving_spec_verify", "serving_spec_verify_kv8",  # + batched verify
                                                # + draft-pool chunk prefill
}


class RetraceError(MXNetError):
    """A watched callable recompiled more often than its budget allows."""


def _bare_name(logged: str) -> str:
    """pxla logs the callable wrapped in its transform, ``jit(step)`` or
    ``pmap(step)``; sinks and PROGRAM_NAMES key on the bare ``step``."""
    for wrapper in ("jit(", "pmap("):
        if logged.startswith(wrapper) and logged.endswith(")"):
            return logged[len(wrapper):-1]
    return logged


class _CompileLogHandler(logging.Handler):
    """Logging handler forwarding compile events to monitor sinks."""

    def __init__(self, monitor: "_CompileLogMonitor"):
        super().__init__(level=logging.DEBUG)
        self._monitor = monitor

    def emit(self, record: logging.LogRecord) -> None:  # pragma: no branch
        try:
            if (isinstance(record.msg, str)
                    and record.msg.startswith(_COMPILE_MSG_PREFIX)
                    and record.args):
                self._monitor._dispatch(_bare_name(str(record.args[0])))
        except Exception:
            # never let accounting break the compile it observes
            pass


class _CompileLogMonitor:
    """Shared tap on JAX's compile log, fanning events out to sinks.

    The logger hook (handler install + level lowering) is managed
    refcounted: installed when the first sink subscribes, restored when
    the last unsubscribes — so a RetraceGuard and the telemetry feed
    (`retraces_total`) can observe the same compiles concurrently
    without fighting over the logger state.
    """

    def __init__(self):
        self._sinks = []
        self._lock = threading.Lock()
        self._handler: Optional[_CompileLogHandler] = None
        self._prev_level: Optional[int] = None
        self._prev_propagate: bool = True

    def _dispatch(self, name: str) -> None:
        for sink in list(self._sinks):
            try:
                sink(name)
            except Exception:
                pass

    def subscribe(self, sink) -> None:
        with self._lock:
            self._sinks.append(sink)
            if self._handler is not None:
                return
            logger = logging.getLogger(_COMPILE_LOGGER)
            self._handler = _CompileLogHandler(self)
            # the compile line is emitted at DEBUG unless jax_log_compiles
            # is set; lower the logger (not the root) so it reaches our
            # handler, and stop propagation so the records we forced into
            # existence don't spam the root handlers
            if logger.getEffectiveLevel() > logging.DEBUG:
                self._prev_level = logger.level
                self._prev_propagate = logger.propagate
                logger.propagate = False
                logger.setLevel(logging.DEBUG)
            logger.addHandler(self._handler)

    def unsubscribe(self, sink) -> None:
        with self._lock:
            try:
                self._sinks.remove(sink)
            except ValueError:
                return
            if self._sinks or self._handler is None:
                return
            logger = logging.getLogger(_COMPILE_LOGGER)
            logger.removeHandler(self._handler)
            self._handler = None
            if self._prev_level is not None:
                logger.setLevel(self._prev_level)
                logger.propagate = self._prev_propagate
                self._prev_level = None


_monitor = _CompileLogMonitor()


def subscribe_compiles(sink) -> None:
    """Register ``sink(program_name)`` for every observed compilation."""
    _monitor.subscribe(sink)


def unsubscribe_compiles(sink) -> None:
    _monitor.unsubscribe(sink)


def _telemetry_sink(name: str) -> None:
    # lazy import: telemetry.enable() is what installs this feed, so the
    # module is importable by the first event; the counter/gauge calls
    # no-op if telemetry was disabled again before an event arrives
    from . import telemetry

    telemetry.counter("retraces_total").inc()
    telemetry.gauge("retrace_compiles", labels={"program": name}).inc()


_feed_installed = False


def install_telemetry_feed() -> None:
    """Feed compile counts into telemetry (`retraces_total` counter +
    per-program `retrace_compiles` gauges) — guard-independent, so a
    production run with telemetry enabled sees compile churn without
    wrapping anything in a RetraceGuard."""
    global _feed_installed
    if not _feed_installed:
        _feed_installed = True
        _monitor.subscribe(_telemetry_sink)


def remove_telemetry_feed() -> None:
    global _feed_installed
    if _feed_installed:
        _feed_installed = False
        _monitor.unsubscribe(_telemetry_sink)


class RetraceGuard:
    """Context manager that raises when compilations exceed a budget.

    Parameters
    ----------
    budget : int
        Max compilations allowed per watched name while the guard is
        active.  Defaults to ``MXTPU_RETRACE_BUDGET`` (64).
    watch : iterable of str, optional
        If given, only these callable names count toward the budget;
        all names are still tallied in :attr:`counts` for diagnosis.
    exempt : iterable of str, optional
        Names never counted toward the budget (applied after ``watch``).
    """

    def __init__(self, budget: Optional[int] = None,
                 watch: Optional[Iterable[str]] = None,
                 exempt: Iterable[str] = ()):
        self.budget = DEFAULT_BUDGET if budget is None else int(budget)
        self.watch = None if watch is None else set(watch)
        self.exempt = set(exempt)
        self.counts: Counter = Counter()
        self._lock = threading.Lock()

    # -- accounting --------------------------------------------------
    def _record(self, name: str) -> None:
        with self._lock:
            self.counts[name] += 1

    def _counted(self, name: str) -> bool:
        if name in self.exempt:
            return False
        return self.watch is None or name in self.watch

    def violations(self) -> Dict[str, int]:
        """Watched names whose compile count exceeds the budget."""
        with self._lock:
            return {n: c for n, c in self.counts.items()
                    if self._counted(n) and c > self.budget}

    def check(self) -> None:
        """Raise :class:`RetraceError` if any watched name is over budget."""
        bad = self.violations()
        if bad:
            detail = ", ".join(f"{n}: {c} compiles"
                               for n, c in sorted(bad.items()))
            raise RetraceError(
                f"retrace budget exceeded (budget={self.budget}): {detail}. "
                "Likely causes: shape-unstable inputs (pad to fixed shapes), "
                "python scalars that vary per step (pass arrays or mark "
                "static), or re-creating jitted closures inside the loop. "
                "Raise MXTPU_RETRACE_BUDGET if the workload legitimately "
                "needs more compilations.")

    # -- context management ------------------------------------------
    def __enter__(self) -> "RetraceGuard":
        _monitor.subscribe(self._record)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        _monitor.unsubscribe(self._record)
        if exc_type is None:
            self.check()
