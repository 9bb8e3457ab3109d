"""CPU rehearsal of a whole run at a tiny size: `perf/run.py` with the look
for a chip skipped, the sizes overridden and, because a CPU trace has no
device plane, the host's XLA:CPU thunk events put in its place.  It finds
wrong paths, arguments and control flow before a chip call does; nothing
it prints is a measurement (the result's device says `cpu`).

    JAX_PLATFORMS=cpu python perf/tests/rehearse.py <cell> [seed] [seconds] [trace]

A later PR that adds a cell adds its tiny sizes to `perf/tests/tiny/<cell>.json`
(keys: a file under perf/ -> the keys to override), nothing here.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perf import run, trace_reduce  # noqa: E402

_load_json, _read_xplane = run.load_json, trace_reduce.read_xplane


def _tiny(cell: str) -> dict:
    with open(os.path.join(HERE, "tiny", cell + ".json")) as f:
        return json.load(f)


def _read_cpu_xplane(path):
    planes = _read_xplane(path)
    host = planes["host"]
    planes["devices"]["/device:TPU:0"] = {
        "ops": [(n, "", s, d) for n, t, s, d in host
                if t.startswith("tf_XLAPjRtCpuClient")],
        "modules": [(n, "", s, d) for n, t, s, d in host
                    if n.startswith("PjitFunction")]}
    return planes


def run_tiny(cell: str, seed=3000000019, seconds=2.0, trace=0) -> dict:
    """The run's result line, parsed; standard output is passed on."""
    tiny = _tiny(cell)

    def load_json(*parts):
        d = _load_json(*parts)
        key = "/".join(parts)
        if key == "peaks.json":
            d["devices"]["cpu"] = d["devices"]["TPU v5 lite"]
        for k, v in tiny.get(key, {}).items():
            if isinstance(v, dict) and isinstance(d.get(k), dict):
                d[k] = dict(d[k], **v)
            else:
                d[k] = v
        return d

    run.load_json = load_json
    run.device_record = lambda chips: {"platform": "cpu", "kind": "cpu",
                                       "count": chips}
    trace_reduce.read_xplane = _read_cpu_xplane
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)])
    finally:
        run.load_json = _load_json
        trace_reduce.read_xplane = _read_xplane
        sys.stdout.write(out.getvalue())
    return json.loads(out.getvalue().strip().splitlines()[-1])


if __name__ == "__main__":
    a = sys.argv[1:]
    run_tiny(a[0], *(float(x) if i == 1 else int(x)
                     for i, x in enumerate(a[1:4])))
    sys.stdout.flush()
    os._exit(0)
