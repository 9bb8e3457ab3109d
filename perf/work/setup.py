"""A process's start by phase, as the program's own set-up record saw it:
the spans of `incubator_mxnet_tpu.telemetry.profiler.setup_spans()`.

Set-up is `[t_open - setup_s, t_open]` on `time.monotonic()`, the clock of
the record and of `record["t_open"]`.  Every instant of it is placed once,
in this order, so that the five phases and `unplaced_s` add up to `setup_s`:

    compile     inside a `compile.backend` span of any thread: XLA's
                compile or the persistent cache's load
    lower       inside a `compile.trace` or `compile.lower` span: JAX's
                trace and lowering (a Pallas kernel's to Mosaic), paid at
                every start whatever the cache holds
    params      in the self time of `setup.initialize`, `.deferred_init`,
                `.cast` or `.set_data`: gluon's parameters
    engine      in the self time of `setup.engine` or `.programs`: pools,
                gathered or stacked weights, host packing
    warmup      from the first `setup.first_call.*` to the window's open:
                the warm-up wave's serving
    unplaced_s  the rest: the interpreter and imports (`setup.import`, the
                package's own, is printed apart), the backend's start, the
                benchmark's draw of the seed's weights, a first forward

The compile spans of all threads are taken as one union, so a jit traced
inside another's trace counts once; a phase's self time is its span less
what its children (phases and compile spans) cover.  One JSON line, `setup_phases`,
says it all: seconds by phase, compile seconds by the phase they ran in,
the ten programs that took longest with their cache hits and misses, how
often each serving program was lowered, and the device's memory at each
top-level phase's close.  A program without the record (or with it
switched off) gives None, and the reason: a reader then leaves its metric
out.
"""
from __future__ import annotations

import json
from collections import defaultdict

PARAMS = ("initialize", "deferred_init", "cast", "set_data")
ENGINE = ("engine", "programs")
LOWER = ("compile.trace", "compile.lower")
COMPILE = ("compile.backend",)
PHASES = ("lower", "compile", "params", "engine", "warmup")


# -- sets of instants: sorted lists of disjoint (lo, hi) ------------------ #
def union(intervals) -> list:
    out = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def minus(a: list, b: list) -> list:
    """`a` less `b`, both unions."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        cur, k = lo, j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def measure(a: list) -> float:
    return sum(hi - lo for lo, hi in a)


def program(fun_name: str) -> str:
    """JAX names a program `f` in its trace and `jit(f)` once lowered."""
    return fun_name[4:-1] if fun_name.startswith("jit(") else fun_name


# -- the partition --------------------------------------------------------- #
def partition(spans: list, t0: float, t1: float) -> dict:
    """Seconds by phase of `[t0, t1]` (`PHASES`, and `unplaced_s`: with
    them it adds up to `t1 - t0`), and what the detail line says beside
    them."""
    spans = [dict(s, t0=max(s["t0"], t0), t1=min(s["t1"], t1))
             for s in spans if s["t1"] > t0 and s["t0"] < t1]
    children = defaultdict(list)
    for s in spans:
        children[s["parent"]].append((s["t0"], s["t1"]))

    def of(names, self_time=False):
        return union(iv for s in spans if s["name"] in names
                     for iv in (minus([(s["t0"], s["t1"])],
                                      union(children[s["id"]]))
                                if self_time else [(s["t0"], s["t1"])]))

    placed, sets = [], {}
    firsts = [s["t0"] for s in spans if s["name"].startswith("first_call.")]
    for phase, got in (
            ("compile", of(COMPILE)),
            ("lower", of(LOWER)),
            ("params", of(PARAMS, self_time=True)),
            ("engine", of(ENGINE, self_time=True)),
            ("warmup", [(min(firsts), t1)] if firsts else [])):
        sets[phase] = minus(got, placed)
        placed = union(placed + sets[phase])
    return {"seconds": {p: measure(sets[p]) for p in PHASES},
            "unplaced_s": measure(minus([(t0, t1)], placed)),
            "import_s": measure(minus(of(("import",), True), placed)),
            "compile_by_phase": compile_by_phase(spans),
            "programs": programs(spans),
            "memory_at_close": {s["name"]: [s["bytes_in_use"],
                                            s.get("peak_bytes_in_use")]
                                for s in spans if "bytes_in_use" in s},
            "spans": len(spans)}


def compile_by_phase(spans: list) -> dict:
    """Seconds of lowering and of compile under each phase they ran in
    (`-`: none open in their thread)."""
    names = {s["id"]: s["name"] for s in spans}
    groups = defaultdict(list)
    for s in spans:
        if s["name"] in LOWER + COMPILE:
            groups[names.get(s["parent"], "-")].append(s)
    out = {}
    for phase, group in groups.items():
        backend = union((s["t0"], s["t1"]) for s in group
                        if s["name"] in COMPILE)
        lower = union((s["t0"], s["t1"]) for s in group
                      if s["name"] in LOWER)
        out[phase] = {"lower_s": measure(minus(lower, backend)),
                      "compile_s": measure(backend)}
    return dict(sorted(out.items(), key=lambda kv: -sum(kv[1].values())))


def programs(spans: list, top: int = 10) -> dict:
    """The `top` programs by seconds of their own compile spans (a trace
    that holds another's counts whole), with how often each was traced,
    lowered and compiled and its cache hits and misses; and the lowerings
    of every serving program."""
    by = defaultdict(lambda: {"seconds": 0.0, "traced": 0, "lowered": 0,
                              "compiled": 0, "hits": 0, "misses": 0})
    count = {"compile.trace": "traced", "compile.lower": "lowered",
             "compile.backend": "compiled"}
    for s in spans:
        if s["name"] in count:
            p = by[program(s.get("fun_name", ""))]
            p["seconds"] += s["t1"] - s["t0"]
            p[count[s["name"]]] += 1
            p["hits"] += s.get("hits", 0)
            p["misses"] += s.get("misses", 0)
    ranked = sorted(by.items(), key=lambda kv: -kv[1]["seconds"])
    return {"top": [dict(v, name=k) for k, v in ranked[:top]],
            "serving_lowered": {k: v["lowered"] for k, v in sorted(by.items())
                                if k.startswith("serving_")}}


# -- the record ------------------------------------------------------------ #
def read_record():
    """`(spans, dropped)` of the program's set-up record; a string, the
    reason, where the program keeps none."""
    try:
        from incubator_mxnet_tpu.telemetry import profiler

        return profiler.setup_spans(), profiler.setup_spans_dropped()
    except (ImportError, AttributeError) as e:
        return f"the program keeps no set-up record: {e}"


def place(record: dict):
    """The partition of this run's set-up, its detail line printed; a
    string, the reason, where there is nothing to place."""
    got = read_record()
    if isinstance(got, str):
        return got
    spans, dropped = got
    t1 = record["t_open"]
    t0 = t1 - record["setup_s"]
    if not any(s["t1"] > t0 and s["t0"] < t1 for s in spans):
        return ("the set-up record holds no span of the start (is it "
                "switched off? MXTPU_SERVING_PROFILER=0)")
    out = partition(spans, t0, t1)
    print(json.dumps({"setup_phases": dict(out, setup_s=record["setup_s"],
                                           dropped=dropped)}), flush=True)
    return out


def phases(record: dict, metric: str):
    """`partition`'s result for this run, computed and printed once for all
    the readers; None, with the reason printed, where there is none."""
    if "setup_phases" not in record:
        record["setup_phases"] = place(record)
    got = record["setup_phases"]
    if isinstance(got, str):
        print(json.dumps({metric: {"left_out": got}}), flush=True)
        return None
    return got
