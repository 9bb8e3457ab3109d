"""Training callbacks (ref `python/mxnet/callback.py` [UNVERIFIED],
SURVEY.md §5.5): Speedometer samples/sec lines (the format
`tools/parse_log.py` scrapes), checkpointing, log-validation."""
from __future__ import annotations

import collections
import logging
import time

from . import telemetry

__all__ = ["BatchEndParam", "Speedometer", "MFUMeter", "do_checkpoint",
           "log_train_metric", "LogValidationMetricsCallback",
           "module_checkpoint"]

# ref python/mxnet/model.py BatchEndParam — the record batch callbacks receive
BatchEndParam = collections.namedtuple(
    "BatchEndParam", ["epoch", "nbatch", "eval_metric", "locals"])


class Speedometer:
    """Prints rolling samples/sec every `frequent` batches."""

    def __init__(self, batch_size, frequent=50, auto_reset=True):
        self.batch_size = batch_size
        self.frequent = frequent
        self.init = False
        self.tic = 0
        self.last_count = 0
        self.auto_reset = auto_reset

    def __call__(self, param):
        count = param.nbatch
        if self.last_count > count:
            self.init = False
        self.last_count = count
        if self.init:
            if count % self.frequent == 0:
                elapsed = time.time() - self.tic
                speed = self.frequent * self.batch_size / elapsed
                if telemetry.enabled():
                    # same numbers the log line prints, as metrics; the
                    # printed output stays byte-identical
                    telemetry.gauge("speedometer_samples_per_sec") \
                        .set(speed)
                    telemetry.histogram("speedometer_step_seconds") \
                        .observe(elapsed / self.frequent)
                if param.eval_metric is not None:
                    name_value = param.eval_metric.get_name_value()
                    if self.auto_reset:
                        param.eval_metric.reset_local()
                    msg = self._speed_msg(param, count, speed)
                    for name, value in name_value:
                        msg += f"\t{name}={value:f}"
                    logging.info(msg)
                else:
                    logging.info("Iter[%d] Batch [%d]\tSpeed: %.2f samples/sec",
                                 param.epoch, count, speed)
                self.tic = time.time()
        else:
            self.init = True
            self.tic = time.time()

    def _speed_msg(self, param, count, speed) -> str:
        """Subclass hook: the line prefix before the metric values."""
        return (f"Epoch[{param.epoch}] Batch [{count}]\t"
                f"Speed: {speed:.2f} samples/sec")


# Published per-chip peaks (Google Cloud TPU documentation, one page per
# generation), keyed by a substring of jax's ``device_kind``.  The ONE
# peaks table: bench.py, chip_smoke.py and telemetry.perf read it.
_BF16_PEAKS = [  # bf16 peak FLOP/s
    ("v6e", 918e12), ("v6", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
]

_HBM_PEAKS = [  # peak HBM bandwidth, bytes/s
    ("v6e", 1640e9), ("v6", 1640e9),
    ("v5p", 2765e9),
    ("v5e", 819e9), ("v5 lite", 819e9), ("v5litepod", 819e9),
    ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9),
]


def _device_peak(table, what, device):
    import jax

    dev = device or jax.devices()[0]
    kind = getattr(dev, "device_kind", "").lower()
    for sub, peak in table:
        if sub in kind:
            return peak
    raise ValueError(
        f"no {what} peak known for device kind {dev.device_kind!r} "
        f"(platform {dev.platform!r}): a utilization against a guessed "
        f"peak is meaningless — add the chip to the table in callback.py")


def device_peak_hbm_bytes_per_s(device=None) -> float:
    """Peak HBM bandwidth (bytes/s) of the (first) local accelerator —
    the memory-side roofline denominator (telemetry/perf.py).  Raises
    `ValueError` for a device the table does not know, the CPU
    included."""
    return _device_peak(_HBM_PEAKS, "HBM bandwidth", device)


def device_peak_flops(device=None) -> float:
    """bf16 peak FLOP/s of the (first) local accelerator.  Raises
    `ValueError` for a device the table does not know, the CPU
    included: a silent wrong denominator would fabricate MFU numbers on
    exactly the benchmarks this figure exists for."""
    return _device_peak(_BF16_PEAKS, "bf16 FLOP/s", device)


class MFUMeter(Speedometer):
    """Speedometer that also reports model FLOPs utilization.

    `flops_per_sample`: analytic training FLOPs per sample (≈ 6·params
    per token × tokens for transformers, 3 × fwd-FLOPs for convnets).
    SURVEY.md §5.5 "step-rate/MFU meters" — no reference counterpart
    (MFU is the TPU-era metric of record, BASELINE.json north star).
    Inherits Speedometer's full state machine (epoch rollover, metric
    auto-reset); only the report line differs.
    """

    def __init__(self, batch_size, flops_per_sample, frequent=50,
                 auto_reset=True, peak_flops=None):
        super().__init__(batch_size, frequent, auto_reset)
        self.flops_per_sample = float(flops_per_sample)
        self.peak_flops = peak_flops

    def _speed_msg(self, param, count, speed) -> str:
        if self.peak_flops is None:
            self.peak_flops = device_peak_flops()
        mfu = speed * self.flops_per_sample / self.peak_flops
        return (f"Epoch[{param.epoch}] Batch [{count}]\t"
                f"Speed: {speed:.2f} samples/sec\tMFU: {100 * mfu:.2f}%")


def do_checkpoint(prefix, period=1):
    """Epoch-end checkpoint callback (params + symbol json)."""
    period = int(max(1, period))

    def _callback(iter_no, sym=None, arg=None, aux=None):
        if (iter_no + 1) % period == 0:
            from .utils import serialization

            if sym is not None and hasattr(sym, "save"):
                sym.save(f"{prefix}-symbol.json")
            arrays = {}
            for k, v in (arg or {}).items():
                arrays[f"arg:{k}"] = v
            for k, v in (aux or {}).items():
                arrays[f"aux:{k}"] = v
            serialization.save_ndarrays(f"{prefix}-{iter_no + 1:04d}.params", arrays)
            logging.info("Saved checkpoint to \"%s-%04d.params\"", prefix, iter_no + 1)

    return _callback


module_checkpoint = do_checkpoint


def log_train_metric(period, auto_reset=False):
    def _callback(param):
        if param.nbatch % period == 0 and param.eval_metric is not None:
            name_value = param.eval_metric.get_name_value()
            for name, value in name_value:
                logging.info("Iter[%d] Batch[%d] Train-%s=%f",
                             param.epoch, param.nbatch, name, value)
            if auto_reset:
                param.eval_metric.reset_local()

    return _callback


class LogValidationMetricsCallback:
    def __call__(self, param):
        if param.eval_metric is None:
            return
        for name, value in param.eval_metric.get_name_value():
            logging.info("Epoch[%d] Validation-%s=%f", param.epoch, name, value)
