"""The only place a profiler trace becomes numbers.

Two stages, so that the arithmetic can be checked on a small recorded
trace (`perf/tests/test_trace_reduce.py`):

1. `read_xplane(path)` reads the profiler's `.xplane.pb` with nothing but
   `jax.profiler.ProfileData` into plain lists: per device its operations
   and its program executions, and the host's spans, all on the profile's
   one clock, as `(name, label, start_s, duration_s)`.
2. `reduce(planes, chips)` clips them to the traced window (the host span
   `perf_window` that `Tracer` wraps around it) and returns the busy union,
   the idle gaps by what the host was doing, and time per named operation
   and per program.

Nothing here knows a cell, a configuration or a metric: the readers under
`perf/metrics/` pick what they need out of the reduced trace by name.
"""
from __future__ import annotations

import glob
import os
import time

WINDOW_SPAN = "perf_window"
# stats of a device event that say which source operation it came from;
# joined into the event's label, where the readers look for kernel names
LABEL_STATS = ("tf_op", "long_name", "hlo_category", "hlo_op", "source")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Tracer:
    """Starts and stops the profiler around a traced window and marks the
    window with a host span."""

    def __init__(self, directory: str):
        self.directory = directory
        self._window = None
        self.host_window_s = None

    def start(self):
        import shutil

        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # no per-call Python events
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()
        self._t0 = time.perf_counter()

    def mark(self):
        """Ends the traced window; the profiler runs on until `stop()`,
        which may stall its caller for tens of seconds."""
        self.host_window_s = time.perf_counter() - self._t0
        self._window.__exit__(None, None, None)
        self._window = None

    def stop(self):
        import jax

        if self._window is not None:
            self.mark()
        jax.profiler.stop_trace()

    def reduce(self, chips: int) -> dict:
        paths = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not paths:
            raise RuntimeError(f"no trace was written to {self.directory}")
        import json

        path = max(paths, key=os.path.getmtime)
        t0 = time.perf_counter()
        planes = read_xplane(path)
        t1 = time.perf_counter()
        reduced = reduce(planes, chips)
        print(json.dumps({"trace": {
            "file_bytes": os.path.getsize(path), "read_s": t1 - t0,
            "reduce_s": time.perf_counter() - t1,
            "device_events": sum(len(d["ops"]) for d in
                                 planes["devices"].values()),
            "host_events": len(planes["host"]),
            "host_window_s": self.host_window_s,
            "modules": reduced["modules"]}}), flush=True)
        keep = os.environ.get("PERF_TRACE_EXCERPT")
        if keep:   # a recorded trace for perf/tests/data
            with open(keep, "w") as f:
                json.dump({"planes": excerpt(planes, float(os.environ.get(
                    "PERF_TRACE_EXCERPT_S", "0.004")))}, f)
        dump = os.environ.get("PERF_TRACE_DUMP")
        if dump:   # for a builder looking at names; no run depends on it
            ops = sorted(reduced["ops"].items(),
                         key=lambda kv: -kv[1]["seconds"])
            host = {}
            for name, thread, _s, d in planes["host"]:
                h = host.setdefault((name, thread), [0, 0.0])
                h[0] += 1
                h[1] += d
            with open(dump, "w") as f:
                json.dump({
                    "stat_keys": planes.get("stat_keys"),
                    "window_s": reduced["window_s"],
                    "busy_s": reduced["busy_s"],
                    "modules": reduced["modules"],
                    "ops_top": ops[:80],
                    "ops_custom": [kv for kv in ops if "custom" in
                                   (kv[0] + kv[1]["label"]).lower()][:80],
                    "host_top": sorted(
                        ([n, t, c, d] for (n, t), (c, d) in host.items()),
                        key=lambda r: -r[3])[:60],
                    "breakdown": reduced["breakdown"]}, f, indent=1)
        return reduced


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "stat_keys": {}}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = out["devices"].setdefault(
                plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                into = dev["ops" if line.name == OPS_LINE else "modules"]
                for e in line.events:
                    stats = dict(e.stats)
                    if len(out["stat_keys"]) < 40:
                        out["stat_keys"].update(
                            {k: str(v)[:80] for k, v in stats.items()})
                    label = " ".join(str(stats[k]) for k in LABEL_STATS
                                     if stats.get(k))
                    # a TPU op's event name is its whole HLO instruction:
                    # the result's name is the op's name, the rest is label
                    name, _, rest = e.name.partition(" = ")
                    into.append((name.lstrip("%"), (rest + " " + label)[:600],
                                 e.start_ns * 1e-9, e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        out["host"].append((e.name, line.name,
                                            e.start_ns * 1e-9,
                                            e.duration_ns * 1e-9))
    return out


def excerpt(planes: dict, seconds: float, label_chars: int = 60) -> dict:
    """The first `seconds` of the traced window, in `read_xplane`'s form and
    small enough to keep as a test's recorded trace: device events that
    start inside it, host spans of at least `MIN_HOST_SPAN_S` that touch
    it, the window's span cut to it."""
    lo = next(h[2] for h in planes["host"] if h[0] == WINDOW_SPAN)
    hi = lo + seconds
    cut = lambda evs: [[n, lab[:label_chars], s, d] for n, lab, s, d in evs
                       if lo <= s < hi]
    return {
        "devices": {k: {"ops": cut(v["ops"]), "modules": cut(v["modules"])}
                    for k, v in planes["devices"].items()},
        "host": [[WINDOW_SPAN, "python", lo, seconds]] + [
            [n, t, s, d] for n, t, s, d in planes["host"]
            if n != WINDOW_SPAN and d >= MIN_HOST_SPAN_S
            and s < hi and s + d > lo]}


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def clip(events: list, lo: float, hi: float) -> list:
    """Events cut to [lo, hi): (name, label, start, duration)."""
    out = []
    for name, label, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, label, a, b - a))
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle [start, end) intervals of a merged busy list in [lo, hi)."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


# idle gaps are attributed one by one only down to this many (the longest)
# and only to host spans at least this long; the rest is summed as `short`
ATTRIBUTED_GAPS = 2000
MIN_HOST_SPAN_S = 20e-6


def attribute(host: list, spans: list) -> list:
    """For each [a, b): the host span that covers most of it; the innermost
    (shortest) among those that cover equally much.  `idle` where none
    does."""
    import numpy as np

    keep = [h for h in host if h[0] != WINDOW_SPAN]
    if not keep or not spans:
        return ["idle"] * len(spans)
    start = np.array([h[2] for h in keep])
    dur = np.array([h[3] for h in keep])
    end = start + dur
    out = []
    for a, b in spans:
        cov = np.minimum(end, b) - np.maximum(start, a)
        best = cov.max()
        if best <= 0:
            out.append("idle")
            continue
        # among those that cover (to rounding) as much, the shortest span
        near = np.flatnonzero(cov >= best * (1 - 1e-9))
        out.append(keep[near[np.argmin(dur[near])]][0])
    return out


def family(name: str, label: str) -> str:
    """An operation's kind and result shape, for the breakdown: `copy.301`
    yielding `bf16[2049,16,16,64]{...}` is one of the `copy
    bf16[2049,16,16,64]`, with all its numbered siblings."""
    import re

    kind = re.sub(r"[.\d]+$", "", name) or name
    shape = re.search(r"[a-z]+\d*\[[\d,]*\]", label)
    return kind + (" " + shape.group(0) if shape else "")


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce(planes: dict, chips: int) -> dict:
    marks = [h for h in planes["host"] if h[0] == WINDOW_SPAN]
    if not marks:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo = marks[0][2]
    hi = lo + marks[0][3]
    long_host = [h for h in clip(planes["host"], lo, hi)
                 if h[3] >= MIN_HOST_SPAN_S]
    names = sorted(planes["devices"])[:chips]
    if not names:
        raise RuntimeError("the trace holds no device plane")
    busy_s, op_s, op_n, op_label, idle_by = [], {}, {}, {}, {}
    modules = {}
    for dev in names:
        ops = clip(planes["devices"][dev]["ops"], lo, hi)
        busy = union([(s, s + d) for _, _, s, d in ops])
        busy_s.append(sum(b - a for a, b in busy))
        for name, label, _s, d in ops:
            op_s[name] = op_s.get(name, 0.0) + d
            op_n[name] = op_n.get(name, 0) + 1
            op_label[name] = label
        idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
        for (a, b), who in zip(idle, attribute(long_host,
                                               idle[:ATTRIBUTED_GAPS])):
            idle_by[who] = idle_by.get(who, 0.0) + (b - a)
        rest = sum(b - a for a, b in idle[ATTRIBUTED_GAPS:])
        if rest:
            idle_by["short gaps"] = idle_by.get("short gaps", 0.0) + rest
        for name, _label, _s, d in clip(
                planes["devices"][dev]["modules"], lo, hi):
            m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += d
    n = len(names)
    by_family = {}
    for k, v in op_s.items():
        f = family(k, op_label[k])
        by_family[f] = by_family.get(f, 0.0) + v / n
    if sum(busy_s) <= 0:
        raise RuntimeError("no operation ran on the device in the trace")
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_s) / n,
        "devices": n,
        # per operation name, summed over the window and the devices
        "ops": {k: {"seconds": op_s[k], "count": op_n[k],
                    "label": op_label[k]} for k in op_s},
        "modules": modules,
        "breakdown": {
            "device_ops": top(by_family),
            "idle_gaps": top({k: v / n for k, v in idle_by.items()}),
        },
    }


def ops_matching(reduced: dict, *needles: str) -> dict:
    """The reduced trace's operations whose name or label holds every
    needle: {"seconds", "count"} summed; None where there is none."""
    sec, cnt = 0.0, 0
    for name, op in reduced["ops"].items():
        text = name + " " + op["label"]
        if all(n in text for n in needles):
            sec += op["seconds"]
            cnt += op["count"]
    return {"seconds": sec, "count": cnt} if cnt else None


def idle_pct(reduced: dict) -> float:
    """Share of the traced window in which no operation ran on the device."""
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def program_mean_ms(reduced: dict, needle: str):
    """Mean device time of one execution of the programs whose name holds
    `needle`, in ms; None where none ran."""
    hit = [m for name, m in reduced["modules"].items() if needle in name]
    count = sum(m["count"] for m in hit)
    return 1e3 * sum(m["seconds"] for m in hit) / count if count else None
