"""telemetry.perf: roofline/MFU program attribution and device-memory
watermarks (ISSUE 8 tentpole) — capture from real compiled programs,
achieved-rate gauges, per-device shard attribution, and the background
watermark poller.  (The int8-vs-bf16 dot byte ordering is a claim about
the TPU program and is asked of the v5e compiler in
test_chip_compile.py.)"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.telemetry import perf


@pytest.fixture
def tel():
    telemetry.enable()
    telemetry.get_registry().clear()
    telemetry.tracer.clear()
    perf.clear()
    yield telemetry
    perf.clear()
    telemetry.get_registry().clear()
    telemetry.tracer.clear()
    telemetry.disable()


def _dot(dtype=jnp.float32):
    a = jnp.ones((64, 64), dtype)
    b = jnp.ones((64, 64), dtype)
    return jax.jit(lambda x, y: x @ y), (a, b)


# --------------------------------------------------------------------- #
# capture / note_timing / roofline_table
# --------------------------------------------------------------------- #
def test_capture_extracts_cost_and_memory_analysis(tel):
    fn, args = _dot()
    pc = perf.capture("matmul64", fn, *args)
    assert pc is not None
    # 64³ MACs → 2·64³ flops, and three 64×64 f32 buffers move
    assert pc.flops == pytest.approx(2 * 64**3, rel=0.1)
    assert pc.bytes_accessed >= 3 * 64 * 64 * 4 * 0.5
    assert pc.expected_bytes > 0
    assert pc.bound_by() in ("compute", "memory")
    assert math.isfinite(pc.intensity) and pc.intensity > 0
    reg = tel.get_registry()
    assert reg.get("program_flops", {"program": "matmul64"}).value == pc.flops
    assert reg.get("program_hbm_bytes",
                   {"program": "matmul64"}).value == pc.bytes_accessed
    assert reg.get("program_expected_bytes",
                   {"program": "matmul64"}).value == pc.expected_bytes


def test_capture_is_once_per_name_unless_forced(tel):
    fn, args = _dot()
    pc1 = perf.capture("once", fn, *args)
    fn2, args2 = _dot(jnp.bfloat16)
    pc2 = perf.capture("once", fn2, *args2)
    assert pc2 is pc1  # second capture skipped: same record back
    pc3 = perf.capture("once", fn2, *args2, force=True)
    assert pc3 is not pc1


def test_note_timing_sets_achieved_rate_gauges(tel):
    fn, args = _dot()
    pc = perf.capture("timed", fn, *args)
    perf.note_timing("timed", 1e-3)
    assert pc.last_seconds == 1e-3
    assert pc.last_mfu == pytest.approx(pc.flops / 1e-3 / perf._peak_flops())
    assert pc.last_gbps == pytest.approx(pc.bytes_accessed / 1e-3 / 1e9)
    assert 0 < pc.last_fraction
    reg = tel.get_registry()
    assert reg.get("program_mfu", {"program": "timed"}).value == pc.last_mfu
    assert reg.get("program_hbm_gbps",
                   {"program": "timed"}).value == pc.last_gbps
    assert reg.get("program_roofline_fraction",
                   {"program": "timed"}).value == pc.last_fraction


def test_note_timing_ignores_uncaptured_and_bad_clock(tel):
    perf.note_timing("ghost", 0.5)       # never captured: no-op
    perf.note_timing(None, 0.5)          # no program: no-op
    fn, args = _dot()
    pc = perf.capture("clocked", fn, *args)
    perf.note_timing("clocked", 0.0)     # non-positive clock: no-op
    assert pc.last_seconds is None
    assert tel.get_registry().get("program_mfu", {"program": "ghost"}) is None


def test_roofline_table_rows_are_name_sorted(tel):
    fn, args = _dot()
    perf.capture("b_prog", fn, *args)
    perf.capture("a_prog", fn, *args, force=True)
    rows = perf.roofline_table()
    assert [r["program"] for r in rows] == ["a_prog", "b_prog"]
    for r in rows:
        assert set(r) >= {"program", "flops", "hbm_bytes", "intensity",
                          "bound_by", "mfu", "hbm_gbps", "roofline_fraction"}


# --------------------------------------------------------------------- #
# trainer integration
# --------------------------------------------------------------------- #
def test_trainer_full_step_is_attributed(tel):
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import Trainer, nn
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    class M(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.d = nn.Dense(4, in_units=6)

        def forward(self, x):
            h = self.d(x)
            return (h * h).mean()

    mx.random.seed(0)
    m = M()
    m.initialize()
    m.hybridize()
    tr = Trainer(m.collect_params(), "sgd", {"learning_rate": 0.1})
    x = NDArray(jnp.ones((2, 6)))
    for _ in range(2):
        with autograd.record():
            loss = m(x)
        loss.backward()
        tr.step(2)
    tr.flush()
    assert tr._perf_program == "trainer_full_step"
    pc = perf.programs().get("trainer_full_step")
    assert pc is not None and pc.flops > 0
    assert pc.last_seconds is not None  # step() fed note_timing
    # re-capture from the retention-free aval skeleton (bench's path)
    assert tr.capture_step_costs() == "trainer_full_step"


def test_trainer_capture_step_costs_without_ctx(tel):
    from incubator_mxnet_tpu.gluon import Trainer, nn

    net = nn.Dense(2, in_units=2)
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    assert tr.capture_step_costs() is None  # no full-step ctx yet


# --------------------------------------------------------------------- #
# device-memory watermarks
# --------------------------------------------------------------------- #
def test_per_device_bytes_attributes_shards(tel):
    x = jnp.ones((16, 4), jnp.float32)
    y = jnp.ones((8,), jnp.int32)
    per = perf.per_device_bytes({"a": x, "b": [y]})
    assert per, "no devices attributed"
    assert sum(per.values()) == 16 * 4 * 4 + 8 * 4
    assert perf.per_device_bytes(None) == {}


def test_sample_device_memory_and_peak_tracking(tel):
    keep = jnp.ones((128, 128), jnp.float32)  # pin live bytes
    perf.reset_peaks()
    s1 = perf.sample_device_memory()
    assert s1, "no devices sampled"
    # look at the device actually holding `keep` (the test harness fakes
    # 8 virtual CPU devices; the others legitimately read 0)
    k = perf._dev_key(next(iter(keep.addressable_shards)).device)
    rec = s1[k]
    assert rec["source"] in ("memory_stats", "live_arrays")
    assert rec["bytes_in_use"] >= keep.nbytes
    assert rec["peak_bytes"] >= rec["bytes_in_use"]
    reg = tel.get_registry()
    assert reg.get("device_bytes_in_use", {"device": k}).value \
        == rec["bytes_in_use"]
    assert reg.get("device_peak_bytes", {"device": k}).value \
        == rec["peak_bytes"]
    peak_before = rec["peak_bytes"]
    del keep
    s2 = perf.sample_device_memory()
    assert s2[k]["peak_bytes"] >= peak_before  # the watermark never drops


def test_sample_device_memory_disabled_is_empty():
    telemetry.disable()
    assert perf.sample_device_memory() == {}


def test_watermark_poller_runs_and_stops(tel):
    assert perf.start_poller(interval=0.05)
    assert perf.start_poller(interval=0.05)  # idempotent
    try:
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if tel.get_registry().get(
                    "device_bytes_in_use",
                    {"device": perf._dev_key(jax.devices()[0])}) is not None:
                break
            time.sleep(0.05)
        else:
            raise AssertionError("poller never published a sample")
    finally:
        perf.stop_poller()
    assert perf._poller is None


def test_gate_style_state_watermark_consistency(tel):
    """The cross-check the ZeRO dryrun gate runs, at single-device
    scale: the Trainer's claimed optimizer_state_bytes_per_device must
    match the measured per-device shard attribution of its live state."""
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import Trainer, nn
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    mx.random.seed(0)
    net = nn.Dense(8, in_units=16)
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9})
    x = NDArray(jnp.ones((2, 16)))
    for _ in range(2):
        with autograd.record():
            y = net(x)
            loss = (y * y).sum()
        loss.backward()
        tr.step(2)
    tr.flush()
    tr._sync_states()
    claimed = tr.optimizer_state_bytes_per_device()
    measured = max(perf.per_device_bytes(list(tr._states.values())).values(),
                   default=0)
    assert claimed > 0 and measured > 0
    assert abs(measured - claimed) <= 0.1 * claimed, \
        f"claimed {claimed} vs measured {measured}"
