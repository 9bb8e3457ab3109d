"""The benchmark's one command.

    python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip per process.  Everything that belongs to a cell is
found by the names in `BENCHMARK.json`: the cell's file
`perf/workloads/<cell>.json` (driver, engine or job settings, limits of
`correct`), its configuration `perf/configs/<config>.json` with the plain
reference `perf/reference/<config>.py`, its traffic
`perf/traffic/<traffic>.json` with the generator
`perf/traffic/<generator>.py` that the mix names, and one reader `perf/metrics/<metric>.py`
per per-layer metric.  No name of a cell, a configuration or a metric
appears in this file, in `perf/drivers/` or in `perf/trace_reduce.py`.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `compared` (each number of the `correct` decision
beside its limit).  The same comparisons are the last lines of standard
error.  Everything else goes on earlier lines.
"""
from __future__ import annotations

import time

T_START = time.time()        # set-up is counted from the process's start

import argparse              # noqa: E402
import importlib.util        # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import shutil                # noqa: E402
import sys                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# `python perf/run.py` puts perf/ first on the path; the program and the
# package `perf` are found from the checkout's root instead
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)
OUT = os.path.join(HERE, ".out")


def say(*a):
    print(*a, flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_file(kind: str, name: str):
    """Import `perf/<kind>/<name>.py` by path: names may hold `.` and `-`."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perf_{kind}_" + "".join(c if c.isalnum() else "_" for c in name),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        sys.exit(f"no workload {name!r} in BENCHMARK.json; "
                 f"there are {sorted(cells)}")
    return manifest, cells[name]


def metrics_of(manifest: dict, kind: str, cell: str) -> list:
    """The manifest's metrics of one kind that this cell reports."""
    return [m for m in manifest[kind]
            if cell in m.get("workloads", [cell])]


class CompileCounter:
    """Backend compilations, counted by JAX's own monitoring events; the
    window must see none."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._ev)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _ev(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def build_driver(workload: str, seed: int):
    """What every tool here starts with: the cell's files by name, the
    program imported, a TPU found, the compile cache placed, the driver
    built.  Returns (manifest, its entry of the cell, device, peak, driver).
    In a directory without the program the import fails and the run ends
    with no result."""
    manifest, entry = manifest_cell(workload)
    cell = load_json("workloads", entry["name"] + ".json")
    peaks = load_json("peaks.json")
    from incubator_mxnet_tpu import runtime

    import jax

    device = device_record(entry["chips"])
    if device["kind"] not in peaks["devices"]:
        sys.exit(f"device kind {device['kind']!r} is not in perf/peaks.json")
    cache_dir = runtime.use_compile_cache()
    # the hundreds of small initialisation programs are cached too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    say(json.dumps({"cell": entry["name"], "seed": seed, "device": device,
                    "compile_cache": cache_dir}))
    traffic = dict(load_json("traffic", entry["traffic"] + ".json"),
                   name=entry["traffic"])
    driver = load_file("drivers", cell["driver"]).Driver(
        cell=cell, config=load_json("configs", entry["config"] + ".json"),
        traffic=traffic, seed=seed,
        reference=load_file("reference", entry["config"]),
        generate=load_file("traffic", traffic["generator"]), say=say)
    return manifest, entry, device, peaks["devices"][device["kind"]], driver


def device_record(chips: int) -> dict:
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"perf/run.py needs a TPU: {e}")
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"perf/run.py needs a TPU; JAX found {d.platform!r} "
                 f"({d.device_kind}). A CPU timing is not a measurement "
                 "of this system: there is no fallback.")
    if len(devs) < chips:
        sys.exit(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest, entry, device, peak, driver = build_driver(args.workload,
                                                         args.seed)
    compiles = CompileCounter()
    from perf import trace_reduce

    driver.setup(args.seconds)
    setup_s = time.time() - T_START
    say(json.dumps({"setup_s": setup_s, "compiles_in_setup": compiles.n,
                    "cache_hits_in_setup": compiles.hits}))

    trace_dir = os.path.join(OUT, "trace-" + entry["name"])
    tracer = trace_reduce.Tracer(trace_dir) if args.trace else None
    n0 = compiles.n
    record = driver.run(args.seconds, tracer)
    record["compiles_in_window"] = compiles.n - n0
    record["setup_s"] = setup_s
    record["peak"] = peak
    device["memory_peak_bytes"] = memory_peak(entry["chips"])
    if tracer is not None:
        record["trace"] = tracer.reduce(entry["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    say(json.dumps({"window": {k: v for k, v in record.items()
                               if isinstance(v, (int, float, str))}}))

    # the reference runs last: the peak is read and the program's state
    # is freed before it takes the chip's memory
    driver.release()
    t_chk = time.time()
    compared = driver.check(record)
    compared.append({"name": "compiles_in_window",
                     "value": record["compiles_in_window"], "limit": 0})
    say(json.dumps({"check_s": time.time() - t_chk}))
    correct = all(c["value"] <= c["limit"] for c in compared)

    end_to_end = dict(record["end_to_end"], setup_s=setup_s)
    if args.trace:
        wanted = metrics_of(manifest, "per_layer", entry["name"])
        values = {}
        for m in wanted:
            v = load_file("metrics", m["name"]).read(record)
            if v is not None:   # a reader that finds nothing says nothing
                values[m["name"]] = v
    else:
        wanted = metrics_of(manifest, "end_to_end", entry["name"])
        values = {m["name"]: end_to_end[m["name"]] for m in wanted}
    units = {m["name"]: m["unit"] for m in wanted}
    result = {
        "correct": bool(correct),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in values.items()},
        "device": device,
    }
    if args.trace:
        result["breakdown"] = record["trace"]["breakdown"]
        say(json.dumps({"end_to_end_in_traced_run": end_to_end}))
    result["compared"] = compared
    for c in compared:
        print(f"compared {c['name']}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    # nothing may outlive the run: daemon threads of the program's
    # telemetry do not get a say
    os._exit(rc)
