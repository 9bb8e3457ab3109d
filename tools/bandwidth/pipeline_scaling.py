"""C++ image-pipeline thread-scaling measurement (r4 VERDICT item 9).

Writes a synthetic JPEG .rec, then measures decode+augment throughput of
`native/image_pipeline.cc` (via io.ImageRecordIter) at preprocess
threads = 1, 2, 4, 8.

On a multi-core TPU host the aggregate should scale ~linearly until the
cores run out; on THIS sandbox's single CPU core, linear scaling is
physically impossible — what the run proves instead is that adding
workers does not COLLAPSE aggregate throughput (no lock contention /
queue serialization in the pipeline), which is the software property
the scaling claim rests on.

    JAX_PLATFORMS=cpu PYTHONPATH=. \
        python tools/bandwidth/pipeline_scaling.py
"""
import argparse
import json
import os
import time

import numpy as onp


def make_rec(path: str, n: int, hw: int = 224, quality: int = 90) -> None:
    from incubator_mxnet_tpu import recordio as rio

    rng = onp.random.RandomState(0)
    # a handful of distinct source images re-packed n times keeps rec
    # generation fast while every record still JPEG-decodes fully
    srcs = [rng.randint(0, 255, (hw, hw, 3), dtype=onp.uint8)
            for _ in range(8)]
    payloads = [rio.pack_img(rio.IRHeader(0, float(i % 10), i, 0),
                             srcs[i % len(srcs)], quality=quality)
                for i in range(len(srcs))]
    w = rio.MXRecordIO(path, "w")
    for i in range(n):
        w.write(payloads[i % len(payloads)])
    w.close()


def measure(rec: str, threads: int, batch: int = 64,
            warm_batches: int = 2, timed_batches: int = 12) -> float:
    from incubator_mxnet_tpu import io as mxio

    it = mxio.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, 224, 224), batch_size=batch,
        preprocess_threads=threads, shuffle=False, device=False)
    n = 0
    for _ in range(warm_batches):
        next(it)
    t0 = time.perf_counter()
    for _ in range(timed_batches):
        b = next(it)
        n += batch
    # touch the data so lazy work can't escape the timer
    onp.asarray(b.data[0].asnumpy()).ravel()[0]
    dt = time.perf_counter() - t0
    return n / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--records", type=int, default=2048)
    args = ap.parse_args()
    rec = "/tmp/pipeline_scaling.rec"
    if not os.path.exists(rec):
        make_rec(rec, args.records)
    rows = []
    for threads in (1, 2, 4, 8):
        ips = measure(rec, threads)
        rows.append({"threads": threads, "images_per_s": round(ips, 1)})
        print(f"threads={threads}: {ips:,.1f} img/s")
    ncores = os.cpu_count()
    result = {"host_cores": ncores, "rows": rows}
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
