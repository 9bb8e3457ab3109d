"""Roofline/MFU attribution + device-memory watermarks (ISSUE 8).

Two pieces of the production performance-observability layer, both
riding the registry's near-zero disabled path (every entry point checks
the module flag first and returns):

**Program cost attribution.**  Every compiled program the repo owns
(Trainer full-step incl. the ZeRO explicit/bucketed tiers, generation's
float and int8 decode programs, the flash-attention benches) is wrapped
with `capture()` at build time: an AOT ``lower().compile()`` whose
``cost_analysis()`` (flops, bytes accessed, transcendentals) and
``memory_analysis()`` (argument/output/temp bytes) land in a per-name
`ProgramCost` record and ``program_flops`` / ``program_hbm_bytes`` /
``program_expected_bytes`` gauges.  `note_timing()` then combines the
record with the host-side step timing the instrumented call sites
already measure (``trainer_step_seconds``, the decode SLO clocks) into
``program_mfu{program=}``, ``program_hbm_gbps{program=}`` and
``program_roofline_fraction{program=}`` — achieved over the roofline
bound ``max(flops/peak_flops, bytes/peak_bw)``.  `roofline_table()`
(tools/roofline_report.py, bench.py BENCH detail) adds arithmetic
intensity and the bound-by classification (intensity vs the device
ridge point).

Known caveat, stated rather than papered over: XLA's HLO cost analysis
models a ``while`` body as executing ONCE, so the flop/byte totals of
scan-shaped decode programs reflect one token step plus prefill — MFU
rows for decode are comparable to each other (the int8-vs-float byte
ratio is exact) but not to the trainer rows.

**Device-memory watermarks.**  `sample_device_memory()` feeds
``device_bytes_in_use{device=}`` / ``device_peak_bytes{device=}`` from
the backend allocator (``device.memory_stats()``) where available and
from an analysis-derived fallback elsewhere (CPU: per-shard byte
attribution over ``jax.live_arrays()`` — aval metadata only, no device
sync).  `per_device_bytes(tree)` attributes one pytree's real shard
bytes per device — the ZeRO dryrun gate cross-checks the Trainer's
``optimizer_state_bytes_per_device`` claim against it.  `poll()` runs
the sampler on a background thread for long jobs.

THE NO-HOST-SYNC RULE applies throughout: everything here reads host
clocks, compile-time analysis results, allocator counters, or
shape/dtype metadata — never device data.
"""
from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from . import registry as _registry_mod

__all__ = ["ProgramCost", "capture", "capture_compiled", "note_timing",
           "recent_timings", "programs", "roofline_table", "clear",
           "set_hlo_text_capture", "hlo_text_capture_enabled",
           "program_hlo", "hlo_texts",
           "sample_device_memory", "per_device_bytes", "reset_peaks",
           "start_poller", "stop_poller"]


def _reg():
    from . import get_registry

    return get_registry()


def _gauge(name, labels=None):
    return _reg().gauge(name, labels)


class ProgramCost:
    """Compile-time cost/memory analysis of one named compiled program,
    plus the latest achieved-timing attribution (`note_timing`)."""

    __slots__ = ("name", "sig", "flops", "bytes_accessed", "transcendentals",
                 "arg_bytes", "out_bytes", "temp_bytes", "code_bytes",
                 "last_seconds", "last_mfu", "last_gbps", "last_fraction")

    def __init__(self, name, sig=None, flops=0.0, bytes_accessed=0.0,
                 transcendentals=0.0, arg_bytes=0, out_bytes=0,
                 temp_bytes=0, code_bytes=0):
        self.name = name
        self.sig = sig
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.transcendentals = float(transcendentals)
        self.arg_bytes = int(arg_bytes)
        self.out_bytes = int(out_bytes)
        self.temp_bytes = int(temp_bytes)
        self.code_bytes = int(code_bytes)
        self.last_seconds = None
        self.last_mfu = None
        self.last_gbps = None
        self.last_fraction = None

    @property
    def intensity(self) -> float:
        """Arithmetic intensity, flops per HBM byte."""
        return self.flops / self.bytes_accessed if self.bytes_accessed \
            else math.inf

    @property
    def expected_bytes(self) -> int:
        """Expected live-footprint of one execution (argument + output +
        temp bytes from `memory_analysis()`)."""
        return self.arg_bytes + self.out_bytes + self.temp_bytes

    def bound_by(self) -> str:
        """Roofline classification: ridge point = peak_flops/peak_bw."""
        ridge = _peak_flops() / max(1.0, _peak_hbm())
        return "compute" if self.intensity >= ridge else "memory"

    def as_dict(self) -> dict:
        return {
            "program": self.name,
            "flops": self.flops,
            "hbm_bytes": self.bytes_accessed,
            "transcendentals": self.transcendentals,
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "temp_bytes": self.temp_bytes,
            "intensity": round(self.intensity, 3)
            if math.isfinite(self.intensity) else None,
            "bound_by": self.bound_by(),
            "seconds": self.last_seconds,
            "mfu": self.last_mfu,
            "hbm_gbps": self.last_gbps,
            "roofline_fraction": self.last_fraction,
        }


_programs: Dict[str, ProgramCost] = {}
_lock = threading.Lock()
_peaks_cache: Dict[str, float] = {}
# per-execution timing events for the merged profiler timeline
# (lock-free: deque appends are atomic; readers copy)
_timings: deque = deque(maxlen=4096)

# ---- program text capture (the hlolint contract-gate feed) ----------- #
# Off by default: program texts run to hundreds of KB and only the
# contract gate / ad-hoc inspection wants them.  The same AOT compile
# that feeds cost analysis serves them — no extra compilation.
_hlo_texts: Dict[str, Dict[str, str]] = {}
_hlo_text_capture: Optional[bool] = None


def set_hlo_text_capture(on: Optional[bool]) -> None:
    """Force program-text capture on/off (None = defer to the
    ``MXTPU_HLO_TEXT_CAPTURE`` env)."""
    global _hlo_text_capture
    _hlo_text_capture = on


def hlo_text_capture_enabled() -> bool:
    if _hlo_text_capture is not None:
        return _hlo_text_capture
    import os

    return os.environ.get("MXTPU_HLO_TEXT_CAPTURE", "").strip().lower() \
        in ("1", "on", "true", "yes")


def _store_hlo_text(program: str, compiled, lowered) -> None:
    texts: Dict[str, str] = {}
    try:
        texts["hlo"] = compiled.as_text()
    except Exception:
        pass
    if lowered is not None:
        try:
            texts["stablehlo"] = lowered.as_text()
        except Exception:
            pass
    if texts:
        with _lock:
            _hlo_texts[program] = texts


def program_hlo(program: str) -> Optional[Dict[str, str]]:
    """Captured program texts for one program name:
    ``{"hlo": <compiled/optimized text>, "stablehlo": <lowered MLIR>}``
    (``stablehlo`` present only when the capture site had the lowered
    stage in hand).  None when never captured."""
    with _lock:
        t = _hlo_texts.get(program)
        return dict(t) if t else None


def hlo_texts() -> Dict[str, Dict[str, str]]:
    with _lock:
        return {k: dict(v) for k, v in _hlo_texts.items()}


# The CPU has no entry in callback's peaks table.  The attribution
# gauges still have to work there (the test suite runs on it), so they
# are taken against these nominal figures; nothing prints them as a
# device's utilization.  An accelerator the table does not know raises.
_NOMINAL_CPU_PEAKS = {"flops": 1e12, "hbm": 100e9}


def _peak(which: str) -> float:
    v = _peaks_cache.get(which)
    if v is None:
        import jax

        from .. import callback

        dev = jax.devices()[0]
        if dev.platform == "cpu":
            v = _NOMINAL_CPU_PEAKS[which]
        elif which == "flops":
            v = float(callback.device_peak_flops(dev))
        else:
            v = float(callback.device_peak_hbm_bytes_per_s(dev))
        _peaks_cache[which] = v
    return v


def _peak_flops() -> float:
    return _peak("flops")


def _peak_hbm() -> float:
    return _peak("hbm")


def _cost_dict(compiled) -> dict:
    """Normalize `compiled.cost_analysis()` across jax versions (list of
    per-computation dicts on 0.4.x, a flat dict on newer)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def capture_compiled(program: str, compiled, sig=None,
                     lowered=None) -> Optional[ProgramCost]:
    """Record the cost/memory analysis of an already-compiled program
    under `program`; sets the per-program compile-time gauges.  Returns
    the record, or None (telemetry off / analysis unavailable — e.g. a
    backend without cost-analysis support).

    When program-text capture is on (`set_hlo_text_capture` /
    ``MXTPU_HLO_TEXT_CAPTURE=1``) the compiled HLO text — and the
    lowered StableHLO when the caller passes its ``lowered`` stage —
    is stored for `program_hlo()`; tools/hlolint and ci/hlolint_gate.py
    read contracts off it, so ONE AOT compile serves roofline, HLO
    capture, and contract checking."""
    if not _registry_mod._enabled:
        return None
    if hlo_text_capture_enabled():
        _store_hlo_text(program, compiled, lowered)
    try:
        cost = _cost_dict(compiled)
    except Exception:
        cost = {}
    arg = out = tmp = code = 0
    try:
        ma = compiled.memory_analysis()
        arg = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
        out = int(getattr(ma, "output_size_in_bytes", 0) or 0)
        tmp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        code = int(getattr(ma, "generated_code_size_in_bytes", 0) or 0)
    except Exception:
        pass
    if not cost and not (arg or out or tmp):
        return None
    pc = ProgramCost(program, sig=sig,
                     flops=cost.get("flops", 0.0) or 0.0,
                     bytes_accessed=cost.get("bytes accessed", 0.0) or 0.0,
                     transcendentals=cost.get("transcendentals", 0.0) or 0.0,
                     arg_bytes=arg, out_bytes=out, temp_bytes=tmp,
                     code_bytes=code)
    with _lock:
        _programs[program] = pc
    lab = {"program": program}
    _gauge("program_flops", lab).set(pc.flops)
    _gauge("program_hbm_bytes", lab).set(pc.bytes_accessed)
    _gauge("program_expected_bytes", lab).set(pc.expected_bytes)
    return pc


def capture(program: str, fn, *args, sig=None, force=False,
            **kwargs) -> Optional[ProgramCost]:
    """AOT ``fn.lower(*args).compile()`` → `capture_compiled`.

    ONE capture per program name (pass ``force=True`` to refresh after
    a signature change): the AOT compile is a second, cache-cold
    compilation of the program — bounding it to the first build keeps
    telemetry-enabled rebuild loops (e.g. the LRU eviction smoke) from
    paying it per signature.  `fn` may be a jitted function or an
    already-lowered ``jax.stages.Lowered``.  Near-zero when disabled.
    """
    if not _registry_mod._enabled:
        return None
    with _lock:
        prev = _programs.get(program)
    if prev is not None and not force:
        return prev
    try:
        lowered = fn if hasattr(fn, "compile") and not hasattr(fn, "lower") \
            else fn.lower(*args, **kwargs)
        compiled = lowered.compile()
    except Exception:
        return None
    return capture_compiled(program, compiled, sig=sig, lowered=lowered)


def note_timing(program: Optional[str], seconds: float) -> None:
    """Combine one host-measured execution time with the program's
    captured cost analysis into the achieved-rate gauges:

    * ``program_mfu{program=}``     — flops / seconds / peak_flops
    * ``program_hbm_gbps{program=}`` — bytes / seconds / 1e9
    * ``program_roofline_fraction{program=}`` — roofline-bound time
      ``max(flops/peak_flops, bytes/peak_bw)`` over measured time
      (1.0 = running at the roofline for whichever resource binds).

    No-op when disabled, when `program` was never captured, or when the
    clock reads non-positive (the timing still lands in the bounded
    `recent_timings` ring for the merged profiler timeline even when
    the program has no cost capture).
    """
    if not _registry_mod._enabled or program is None:
        return
    if seconds and seconds > 0:
        t_end = time.perf_counter()
        _timings.append({"program": program, "t0": t_end - seconds,
                         "dur": seconds})
    with _lock:
        pc = _programs.get(program)
    if pc is None or not seconds or seconds <= 0:
        return
    mfu = pc.flops / seconds / _peak_flops()
    gbps = pc.bytes_accessed / seconds / 1e9
    t_roof = max(pc.flops / _peak_flops(),
                 pc.bytes_accessed / max(1.0, _peak_hbm()))
    frac = t_roof / seconds
    pc.last_seconds = seconds
    pc.last_mfu = mfu
    pc.last_gbps = gbps
    pc.last_fraction = frac
    lab = {"program": program}
    _gauge("program_mfu", lab).set(mfu)
    _gauge("program_hbm_gbps", lab).set(gbps)
    _gauge("program_roofline_fraction", lab).set(frac)


def recent_timings(since: Optional[float] = None) -> List[dict]:
    """Recent per-execution program timings
    (``{"program", "t0", "dur"}``, perf_counter seconds, oldest first)
    — the merged profiler timeline's program lane.  ``since`` keeps
    only executions still in flight at/after that instant."""
    from .profiler import _snap_deque

    out = [dict(e) for e in _snap_deque(_timings)]
    if since is not None:
        out = [e for e in out if e["t0"] + e["dur"] >= since]
    return out


def programs() -> Dict[str, ProgramCost]:
    with _lock:
        return dict(_programs)


def roofline_table() -> List[dict]:
    """Per-program rows (name-sorted): flops, bytes, intensity, achieved
    MFU/GB/s/roofline fraction, bound-by — the tools/roofline_report.py
    table and the bench.py BENCH ``detail.roofline`` payload."""
    with _lock:
        pcs = [_programs[k] for k in sorted(_programs)]
    return [pc.as_dict() for pc in pcs]


def clear() -> None:
    """Drop captured program records and peak caches (tests)."""
    with _lock:
        _programs.clear()
        _hlo_texts.clear()
    _timings.clear()
    _peaks_cache.clear()
    with _mem_lock:
        _peak_bytes.clear()


# --------------------------------------------------------------------- #
# device-memory watermarks
# --------------------------------------------------------------------- #
_peak_bytes: Dict[str, int] = {}
_mem_lock = threading.Lock()
_poller = None


def _dev_key(dev) -> str:
    return f"{getattr(dev, 'platform', 'cpu')}:{getattr(dev, 'id', 0)}"


def _shard_nbytes(shard) -> int:
    """Shard bytes from aval metadata only (shape × itemsize of the
    per-device buffer) — never reads device data."""
    try:
        data = shard.data
        import numpy as onp

        itemsize = int(onp.dtype(data.dtype).itemsize)
        return math.prod(data.shape) * itemsize if data.shape else itemsize
    except Exception:
        return 0


def per_device_bytes(tree) -> Dict[str, int]:
    """Real per-device byte attribution of one pytree's arrays, from
    their addressable shards (sharded leaves contribute only the local
    shard bytes to each device).  Metadata-only — the measured
    counterpart the ZeRO dryrun gate holds
    ``optimizer_state_bytes_per_device`` against."""
    import jax

    per: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            continue
        for sh in shards:
            k = _dev_key(sh.device)
            per[k] = per.get(k, 0) + _shard_nbytes(sh)
    return per


def sample_device_memory(devices=None) -> Dict[str, dict]:
    """One watermark sample per local device, feeding the
    ``device_bytes_in_use{device=}`` / ``device_peak_bytes{device=}``
    gauges.  Backend allocator stats (``device.memory_stats()``) where
    the runtime provides them; the analysis-derived fallback attributes
    live-array shard bytes per device (CPU backends return no allocator
    stats).  Returns ``{device: {"bytes_in_use", "peak_bytes",
    "source"}}``; empty when telemetry is disabled."""
    if not _registry_mod._enabled:
        return {}
    import jax

    devs = list(devices) if devices is not None else jax.local_devices()
    out: Dict[str, dict] = {}
    missing = []
    for d in devs:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            out[_dev_key(d)] = {
                "bytes_in_use": int(stats["bytes_in_use"]),
                "peak_bytes": int(stats.get("peak_bytes_in_use",
                                            stats["bytes_in_use"])),
                "source": "memory_stats",
            }
        else:
            missing.append(d)
    if missing:
        want = {_dev_key(d) for d in missing}
        per: Dict[str, int] = {k: 0 for k in want}
        try:
            live = jax.live_arrays()
        except Exception:
            live = []
        for arr in live:
            shards = getattr(arr, "addressable_shards", None)
            if not shards:
                continue
            for sh in shards:
                k = _dev_key(sh.device)
                if k in want:
                    per[k] += _shard_nbytes(sh)
        for k, b in per.items():
            out[k] = {"bytes_in_use": b, "peak_bytes": b,
                      "source": "live_arrays"}
    with _mem_lock:
        for k, rec in out.items():
            peak = max(_peak_bytes.get(k, 0), rec["peak_bytes"],
                       rec["bytes_in_use"])
            _peak_bytes[k] = peak
            rec["peak_bytes"] = peak
    for k, rec in out.items():
        lab = {"device": k}
        _gauge("device_bytes_in_use", lab).set(rec["bytes_in_use"])
        _gauge("device_peak_bytes", lab).set(rec["peak_bytes"])
    return out


def reset_peaks() -> None:
    with _mem_lock:
        _peak_bytes.clear()


class _Poller:
    def __init__(self, interval: float):
        self.interval = max(0.05, float(interval))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="mxtpu-mem-watermark",
                                        daemon=True)

    def _run(self):
        while not self._stop.wait(self.interval):
            try:
                sample_device_memory()
            except Exception:
                pass  # a dying backend must not kill the poller thread

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=2.0)


def start_poller(interval: float = 1.0) -> bool:
    """Start the background memory-watermark poller (idempotent).
    Returns False (and does nothing) while telemetry is disabled."""
    global _poller
    if not _registry_mod._enabled:
        return False
    if _poller is not None:
        return True
    _poller = _Poller(interval)
    _poller.start()
    return True


def stop_poller() -> None:
    global _poller
    if _poller is not None:
        _poller.stop()
        _poller = None
