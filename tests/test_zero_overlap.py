"""Backward-overlapped bucketed gradient sync (ISSUE 5): the
parallel/overlap.py partitioner + pack/unpack kernels, the HLO schedule
analyzer, the Trainer's bucketed explicit-tier path (parity vs the
monolithic exchange) and the sticky fallback.  Runs on the
8-virtual-CPU mesh from conftest."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import overlap as ov

D = 8


# ---------------------------------------------------------------------------
# bucket partitioner
# ---------------------------------------------------------------------------

def test_partition_reverse_order_and_cap():
    # grads arrive last-layer-first in the backward pass: buckets are
    # built in REVERSE param order so the first bucket's reduce-scatter
    # can issue while earlier layers are still differentiating
    bks = ov.partition_buckets([80, 160, 80, 240], [4, 4, 4, 4],
                               ["a"] * 4, D, cap_bytes=1000)
    assert [b.idxs for b in bks] == [(3,), (2, 1), (0,)]
    assert bks[0].nbytes == 240 * 4
    assert bks[0].chunks == (240 // D,)
    assert bks[1].chunks == (80 // D, 160 // D)


def test_partition_group_key_split():
    # mixed dtypes/mp flags must not share a bucket (packing would
    # promote one side); a key change flushes even under the cap
    bks = ov.partition_buckets([80, 80, 80], [4, 4, 4], ["a", "b", "b"],
                               D, cap_bytes=10**9)
    assert [b.idxs for b in bks] == [(2, 1), (0,)]


def test_partition_oversize_param_gets_own_bucket():
    bks = ov.partition_buckets([8000, 80, 80], [4, 4, 4], ["a"] * 3,
                               D, cap_bytes=1000)
    assert [b.idxs for b in bks] == [(2, 1), (0,)]
    assert bks[1].nbytes == 8000 * 4  # over cap, alone by construction


def test_partition_rejects_unaligned_npad():
    with pytest.raises(ValueError):
        ov.partition_buckets([81], [4], ["a"], D, cap_bytes=1000)


def test_knob_resolution(monkeypatch):
    assert ov.resolve_bucket_bytes(2.0) == 2 << 20
    monkeypatch.setenv("MXTPU_ZERO_BUCKET_MB", "1.5")
    assert ov.resolve_bucket_bytes(None) == int(1.5 * (1 << 20))
    monkeypatch.delenv("MXTPU_ZERO_BUCKET_MB")
    assert ov.resolve_bucket_bytes(None) == int(
        ov.DEFAULT_BUCKET_MB * (1 << 20))
    assert ov.overlap_enabled(True) and not ov.overlap_enabled(False)
    monkeypatch.setenv("MXTPU_ZERO_OVERLAP", "off")
    assert not ov.overlap_enabled(None)
    assert ov.overlap_enabled(True)  # explicit arg beats env
    monkeypatch.setenv("MXTPU_ZERO_OVERLAP", "1")
    assert ov.overlap_enabled(None)


# ---------------------------------------------------------------------------
# interleaved pack layout: bucketed exchange == per-param exchange
# ---------------------------------------------------------------------------

def test_pack_unpack_parity_bit_exact(mesh8):
    key = jax.random.PRNGKey(0)
    sizes = [80, 160, 240]
    gs = [jax.random.normal(jax.random.fold_in(key, i), (s,), jnp.float32)
          for i, s in enumerate(sizes)]

    def per_param(gs):
        return [lax.psum_scatter(g, "data", tiled=True) for g in gs]

    def bucketed(gs):
        b = ov.GradBucket(idxs=(0, 1, 2), chunks=(10, 20, 30), nbytes=0)
        packed = ov.pack_bucket([gs[j] for j in b.idxs], D)
        sh = lax.psum_scatter(packed, "data", tiled=True)
        segs = ov.unpack_shards(sh, b.chunks)
        # return trip: bucketed all_gather must reassemble per-param flats
        flat = lax.all_gather(ov.pack_shards(segs), "data",
                              tiled=True, axis=0)
        return segs, ov.unpack_gathered(flat, b.chunks, D)

    f1 = jax.jit(shard_map(per_param, mesh=mesh8, in_specs=(P(),),
                           out_specs=P("data"), check_vma=False))
    f2 = jax.jit(shard_map(bucketed, mesh=mesh8, in_specs=(P(),),
                           out_specs=(P("data"), P()), check_vma=False))
    want = f1(gs)
    segs, backs = f2(gs)
    for a, b in zip(want, segs):
        # BIT-equal: the interleaved layout reduces the exact same
        # addends in the same shard positions as the per-param exchange
        assert onp.array_equal(onp.asarray(a), onp.asarray(b))
    psum = jax.jit(shard_map(lambda g: lax.psum(g, "data"), mesh=mesh8,
                             in_specs=(P(),), out_specs=P(),
                             check_vma=False))
    for j in range(3):
        onp.testing.assert_allclose(onp.asarray(backs[j]),
                                    onp.asarray(psum(gs[j])))


def test_pack_single_element_short_circuit():
    g = jnp.arange(16, dtype=jnp.float32)
    assert ov.pack_bucket([g], D) is g


# ---------------------------------------------------------------------------
# HLO schedule analyzer
# ---------------------------------------------------------------------------

_SYNTH_HLO = """\
HloModule jit_step, is_scheduled=true

ENTRY %main (p0: f32[64], p1: f32[64]) -> (f32[8], f32[8]) {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  %rs.1 = f32[8]{0} reduce-scatter(%p0), replica_groups={}, dimensions={0}
  %fusion.1 = f32[64]{0} fusion(%p1), kind=kLoop
  %rs.2 = f32[8]{0} reduce-scatter(%fusion.1), replica_groups={}, dimensions={0}
  %fusion.2 = f32[8]{0} fusion(%rs.2), kind=kLoop
  ROOT %t = (f32[8]{0}, f32[8]{0}) tuple(%rs.1, %fusion.2)
}
"""


def test_schedule_analyzer_synthetic():
    st = ov.schedule_overlap_stats(_SYNTH_HLO)
    assert st["n_collectives"] == 2
    first, second = st["per_collective"]
    # rs.1 has fusion.1 (independent compute) after it -> hidden;
    # rs.2's only successor compute is its own descendant -> exposed
    assert first["independent_compute_after"] > 0
    assert second["independent_compute_after"] == 0
    assert 0.0 < st["overlap_fraction"] < 1.0


def test_schedule_analyzer_async_forms():
    hlo = _SYNTH_HLO.replace(
        "%rs.1 = f32[8]{0} reduce-scatter(%p0), replica_groups={}, "
        "dimensions={0}",
        "%rs.1s = f32[8]{0} reduce-scatter-start(%p0), replica_groups={}\n"
        "  %rs.1 = f32[8]{0} reduce-scatter-done(%rs.1s)")
    st = ov.schedule_overlap_stats(hlo)
    assert st["n_collectives"] == 2


# ---------------------------------------------------------------------------
# trace-measured exposure (tools/xprof_summary.py pair attribution)
# ---------------------------------------------------------------------------

def _ev(name, t0, dur):
    from incubator_mxnet_tpu.utils.xplane import XEvent

    return XEvent(name=name, offset_ps=t0, duration_ps=dur)


def test_trace_attribution_async_pair_and_sync():
    from tools.xprof_summary import collective_overlap_from_events

    evs = [
        _ev("all-reduce-start.1", 0, 10),   # wire = [0, 100] via done
        _ev("fusion.1", 0, 120),            # covers the whole transfer
        _ev("all-reduce-done.1", 90, 10),
        _ev("reduce-scatter.2", 200, 100),  # [200,300]; fusion covers half
        _ev("fusion.2", 250, 100),
    ]
    st = collective_overlap_from_events(evs)
    assert st["n_collectives"] == 2
    assert st["comm_seconds"] == pytest.approx(200e-12)
    assert st["hidden_seconds"] == pytest.approx(150e-12)
    assert st["overlap_fraction"] == pytest.approx(0.75)


def test_trace_attribution_suffix_fallback():
    from tools.xprof_summary import collective_overlap_from_events

    # mismatched suffixes (XLA renumbers dones): time-ordered pairing
    st = collective_overlap_from_events(
        [_ev("all-gather-start.5", 0, 5), _ev("all-gather-done.9", 40, 10)])
    assert st["n_collectives"] == 1
    assert st["comm_seconds"] == pytest.approx(50e-12)
    assert st["overlap_fraction"] == 0.0


def test_trace_attribution_no_collectives():
    from tools.xprof_summary import collective_overlap_from_events

    st = collective_overlap_from_events([_ev("fusion.1", 0, 100)])
    assert st["n_collectives"] == 0 and st["overlap_fraction"] == 0.0


# ---------------------------------------------------------------------------
# Trainer integration: bucketed explicit tier
# ---------------------------------------------------------------------------

class _MLPWithLoss(gluon.nn.HybridBlock):
    def __init__(self):
        super().__init__()
        self.d1 = nn.Dense(64, activation="relu", in_units=32)
        self.d2 = nn.Dense(64, activation="relu", in_units=64)
        self.d3 = nn.Dense(8, in_units=64)
        self.loss = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(self, x, y):
        return self.loss(self.d3(self.d2(self.d1(x))), y).mean()


def _train(mesh, steps=3, **trainer_kw):
    onp.random.seed(0)
    mx.random.seed(0)
    net = _MLPWithLoss()
    net.initialize(force_reinit=True)
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-2}, mesh=mesh, zero_stage=1,
                       **trainer_kw)
    tr._capture_hlo = True
    losses = []
    with mesh:
        for s in range(steps):
            rs = onp.random.RandomState(s)
            x = rs.randn(16, 32).astype(onp.float32)
            y = rs.randint(0, 8, (16,)).astype(onp.int32)
            with autograd.record():
                loss = net(mx.nd.array(x), mx.nd.array(y))
            loss.backward()
            tr.step(16)
            losses.append(float(loss.asnumpy()))
    params = {k: v.data().asnumpy() for k, v in net.collect_params().items()}
    return losses, params, tr


def _assert_param_parity(p_a, p_b, exact=True):
    # gluon name counters differ between instantiations (and sorting
    # misaligns once the counter crosses a digit boundary: dense10 <
    # dense9) — pair by insertion order, which is creation order
    for (ka, va), (kb, vb) in zip(p_a.items(), p_b.items()):
        if exact:
            assert onp.array_equal(va, vb), f"not bit-equal: {ka} vs {kb}"
        else:
            onp.testing.assert_allclose(va, vb, rtol=2e-3, atol=1e-4,
                                        err_msg=f"{ka} vs {kb}")


def test_trainer_bucketed_parity_and_hlo(mesh8):
    l_off, p_off, _ = _train(mesh8, zero_overlap=False)
    # tiny cap: the MLP's ~20 KB of grads must split into >= 2 buckets
    l_on, p_on, tr = _train(mesh8, zero_overlap=True, zero_bucket_mb=0.01)
    assert tr._zero_sig() == ("explicit", "data", D)
    assert not tr._zero_overlap_broken
    bks = tr._fullstep_ctx["zero_buckets"]
    assert bks is not None and len(bks) >= 2
    onp.testing.assert_allclose(l_on, l_off, rtol=2e-4, atol=2e-5)
    # the interleaved pack feeds the identical per-param update: exact
    _assert_param_parity(p_off, p_on, exact=True)
    hlo = tr.last_step_hlo
    nrs = (hlo.count(" reduce-scatter(")
           + hlo.count(" reduce-scatter-start("))
    assert nrs == len(bks), "expected one reduce-scatter per bucket"
    st = ov.schedule_overlap_stats(hlo)
    assert st["n_collectives"] == len(bks)
    assert st["overlap_fraction"] > 0.5


def test_trainer_one_bucket_default_cap(mesh8):
    # default 25 MB cap swallows the whole MLP: single bucket, still
    # the bucketed code path, still exact parity
    l_off, p_off, _ = _train(mesh8, zero_overlap=False)
    l_on, p_on, tr = _train(mesh8, zero_overlap=True)
    bks = tr._fullstep_ctx["zero_buckets"]
    assert bks is not None and len(bks) == 1
    onp.testing.assert_allclose(l_on, l_off, rtol=2e-4, atol=2e-5)
    _assert_param_parity(p_off, p_on, exact=True)


def test_trainer_sticky_fallback(mesh8, monkeypatch):
    # a failing bucketed build must fall back to the monolithic
    # exchange (NOT to gspmd), warn once, and stay fallen back
    def boom(*a, **k):
        raise RuntimeError("synthetic pack failure")

    monkeypatch.setattr(ov, "pack_bucket", boom)
    with pytest.warns(UserWarning, match="monolithic"):
        l_on, p_on, tr = _train(mesh8, zero_overlap=True,
                                zero_bucket_mb=0.01)
    assert tr._zero_overlap_broken
    assert tr._overlap_sig() is None  # sticky: no rebuild attempts
    assert tr._zero_sig() == ("explicit", "data", D)  # tier survived
    assert tr._fullstep_ctx["zero_buckets"] is None
    monkeypatch.undo()
    l_off, p_off, _ = _train(mesh8, zero_overlap=False)
    onp.testing.assert_allclose(l_on, l_off, rtol=2e-4, atol=2e-5)
    _assert_param_parity(p_off, p_on, exact=True)


def test_trainer_env_knob_disables(mesh8, monkeypatch):
    monkeypatch.setenv("MXTPU_ZERO_OVERLAP", "0")
    _, _, tr = _train(mesh8)  # zero_overlap unset -> env decides
    assert tr._fullstep_ctx["zero_buckets"] is None
    assert not tr._zero_overlap_broken  # disabled, not broken
