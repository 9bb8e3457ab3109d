"""Tools (im2rec/parse_log/bandwidth) + Estimator handlers
(SURVEY.md §2.8 tools inventory; r1 padded-file finding: estimator)."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.ndarray.ndarray import NDArray

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_im2rec_list_and_pack(tmp_path):
    from PIL import Image

    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            arr = onp.random.RandomState(i).randint(0, 255, (16, 16, 3),
                                                    dtype=onp.uint8)
            Image.fromarray(arr).save(root / cls / f"{i}.jpg")
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    import importlib

    im2rec = importlib.import_module("im2rec")
    prefix = str(tmp_path / "train")
    entries = im2rec.make_list(str(root), prefix, recursive=True)
    assert len(entries) == 6
    n = im2rec.pack(prefix + ".lst", str(root))
    assert n == 6
    # consume through ImageRecordIter
    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               data_shape=(3, 16, 16), batch_size=3,
                               use_native=False)
    b = next(iter(it))
    assert b.data[0].shape == (3, 3, 16, 16)


def test_parse_log(tmp_path):
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    import importlib

    parse_log = importlib.import_module("parse_log")
    log = ("Epoch[0] Batch [50]\tSpeed: 100.5 samples/sec\taccuracy=0.5\n"
           "Epoch[0] Batch [100]\tSpeed: 200.5 samples/sec\taccuracy=0.6\n"
           "Epoch[0] Train-accuracy=0.61\n"
           "Epoch[0] Validation-accuracy=0.55\n")
    res = parse_log.parse(log.splitlines())
    assert len(res["batches"]) == 2
    ep = res["epochs"][0]
    assert ep["mean_speed"] == pytest.approx(150.5)
    assert ep["validation-accuracy"] == pytest.approx(0.55)


@pytest.mark.parametrize("sizes_mb,n_devices",
                         [([0.25], 8), ([0.5, 1.0], 4)],
                         ids=["runs", "runs_and_reports"])
def test_bandwidth_tool(sizes_mb, n_devices):
    """tools/bandwidth/measure.py produces structured GB/s results on the
    CPU mesh (where it measures host memcpy — documented caveat; the
    tool is validated structurally, numbers are meaningful on ICI).
    Nothing here judges how fast the host is: a rate is positive however
    slow the run, and rate times time is the bytes the ring moves."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bw_measure",
        os.path.join(_ROOT, "tools", "bandwidth", "measure.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    res = mod.measure(sizes_mb, n_devices=n_devices, runs=2)
    assert [r["size_mb"] for r in res] == sizes_mb
    n = n_devices
    for r in res:
        assert set(r) == {"size_mb", "time_ms", "GBps"}
        assert r["time_ms"] > 0 and r["GBps"] > 0
        moved = r["size_mb"] * 2 ** 20 / n * 2 * (n - 1) / n / 1e9
        assert r["GBps"] * r["time_ms"] / 1e3 == pytest.approx(moved,
                                                               rel=0.02)


def test_estimator_handlers_and_early_stopping(tmp_path):
    from incubator_mxnet_tpu.gluon import Trainer, loss as loss_mod, nn
    from incubator_mxnet_tpu.gluon.contrib.estimator import (
        CheckpointHandler, EarlyStoppingHandler, Estimator, EventHandler)

    mx.random.seed(0)
    net = nn.Dense(2, in_units=4)
    net.initialize()
    net(NDArray(jnp.ones((2, 4))))
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})

    rng = onp.random.RandomState(0)
    X = rng.randn(32, 4).astype("float32")
    Y = (X.sum(1) > 0).astype("float32")
    batches = [(NDArray(jnp.asarray(X[i:i + 8])), NDArray(jnp.asarray(Y[i:i + 8])))
               for i in range(0, 32, 8)]

    events = []

    class Recorder(EventHandler):
        def train_begin(self, est):
            events.append("train_begin")

        def epoch_end(self, est):
            events.append(f"epoch_end{est.epoch}")

        def train_end(self, est):
            events.append("train_end")

    est = Estimator(net, loss_mod.SoftmaxCrossEntropyLoss(), trainer=trainer,
                    event_handlers=[
                        Recorder(),
                        CheckpointHandler(str(tmp_path), save_best=True,
                                          monitor="accuracy"),
                        EarlyStoppingHandler("accuracy", patience=50)])
    history = est.fit(batches, val_data=batches, epochs=3)
    assert len(history) == 3
    assert "val_accuracy" in history[-1]
    assert events[0] == "train_begin" and events[-1] == "train_end"
    assert os.path.exists(tmp_path / "model-0002.params")
    assert os.path.exists(tmp_path / "model-best.params")


def test_estimator_early_stopping_fires():
    from incubator_mxnet_tpu.gluon import Trainer, loss as loss_mod, nn
    from incubator_mxnet_tpu.gluon.contrib.estimator import (
        EarlyStoppingHandler, Estimator)

    mx.random.seed(1)
    net = nn.Dense(2, in_units=4)
    net.initialize()
    net(NDArray(jnp.ones((2, 4))))
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.0})
    X = NDArray(jnp.zeros((8, 4)))
    Y = NDArray(jnp.zeros((8,)))
    batches = [(X, Y)]
    est = Estimator(net, loss_mod.SoftmaxCrossEntropyLoss(), trainer=trainer,
                    event_handlers=[EarlyStoppingHandler("accuracy",
                                                         patience=2)])
    history = est.fit(batches, val_data=batches, epochs=50)
    assert len(history) < 50  # stopped early (metric flat at lr=0)
