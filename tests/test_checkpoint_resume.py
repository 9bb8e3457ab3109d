"""Checkpoint/resume + elastic autoresume (VERDICT r1 #7; SURVEY.md
§5.3/§5.4 — the build must EXCEED the reference here).

ISSUE 11 additions: async on-device snapshot isolation, manifest
fault-injection (truncation / missing manifest / checksum mismatch /
partially-renamed tmp dir), mesh-resize restore of ZeRO-1 state, the
inflight-aware prune, and write retry-with-backoff."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
import incubator_mxnet_tpu.parallel as par
from incubator_mxnet_tpu import autograd, telemetry
from incubator_mxnet_tpu.gluon import Trainer, nn
from incubator_mxnet_tpu.gluon import zero as zero_mod
from incubator_mxnet_tpu.gluon.block import HybridBlock
from incubator_mxnet_tpu.gluon.utils import shard_batch
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.utils.checkpoint import (CheckpointCorrupt,
                                                  CheckpointManager)

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _train_steps(net, trainer, n, start=1):
    for step in range(start, start + n):
        key = jax.random.PRNGKey(1000 + step)
        x = NDArray(jax.random.normal(key, (2, 6)))
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        trainer.step(1)


def _make(seed=0):
    mx.random.seed(seed)
    net = nn.Dense(4, in_units=6)
    net.initialize()
    net(NDArray(jnp.ones((2, 6))))
    trainer = Trainer(net.collect_params(), "adam", {"learning_rate": 0.05})
    return net, trainer


def test_full_state_roundtrip(tmp_path):
    net, trainer = _make()
    _train_steps(net, trainer, 3)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, net=net, trainer=trainer, iterator_state={"cursor": 42},
             extra={"epoch": 1})
    w_before = net.weight.data().asnumpy()

    net2, trainer2 = _make(seed=9)  # different init — restore must override
    mgr2 = CheckpointManager(str(tmp_path))
    info = mgr2.restore(net=net2, trainer=trainer2)
    assert info["step"] == 3
    assert info["iterator_state"] == {"cursor": 42}
    assert info["extra"] == {"epoch": 1}
    onp.testing.assert_array_equal(net2.weight.data().asnumpy(), w_before)
    # optimizer state (adam m/v + counts) restored
    assert trainer2._optimizer.num_update == trainer._optimizer.num_update
    # continued training is BIT-EXACT vs the uninterrupted run
    _train_steps(net, trainer, 2, start=4)
    _train_steps(net2, trainer2, 2, start=4)
    onp.testing.assert_array_equal(net.weight.data().asnumpy(),
                                   net2.weight.data().asnumpy())


def test_async_save_and_retention(tmp_path):
    net, trainer = _make()
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    for s in (1, 2, 3, 4):
        _train_steps(net, trainer, 1, start=s)
        mgr.save(s, net=net, trainer=trainer)
    mgr.wait()
    assert mgr.all_steps() == [3, 4]  # pruned to keep=2
    assert mgr.latest_step() == 4


def test_close_joins_worker_and_flushes(tmp_path):
    """tpulint TPU012 regression: close() must flush queued saves and
    JOIN the worker (previously the daemon thread was never joined —
    interpreter exit could kill it mid-write)."""
    net, trainer = _make()
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(1, net=net, trainer=trainer)
    worker = mgr._worker
    assert worker is not None and worker.is_alive()
    mgr.close()
    assert not worker.is_alive()          # joined, not abandoned
    assert mgr._worker is None
    assert mgr.all_steps() == [1]         # queued write landed before join
    mgr.close()                           # idempotent
    # save() after close() restarts the worker transparently
    mgr.save(2, net=net, trainer=trainer)
    mgr.close()
    assert mgr.all_steps() == [1, 2]


def test_close_as_context_manager(tmp_path):
    net, trainer = _make()
    with CheckpointManager(str(tmp_path), async_save=True) as mgr:
        mgr.save(1, net=net, trainer=trainer)
    assert mgr._worker is None
    assert mgr.all_steps() == [1]


def test_worker_error_surfaces_on_close(tmp_path):
    """tpulint TPU011 regression: the worker's error handoff is now
    lock-guarded and close()/wait() re-raise the pending exception."""
    net, trainer = _make()
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    boom = RuntimeError("disk full")
    with mgr._err_lock:
        mgr._error = boom                 # as if _drain had failed
    with pytest.raises(RuntimeError, match="disk full"):
        mgr.close()
    # the error is consumed — the manager is usable again
    mgr.save(1, net=net, trainer=trainer)
    mgr.close()
    assert mgr.all_steps() == [1]


def test_kill_and_resume_bit_exact(tmp_path):
    """Kill a training process mid-run; autoresume restarts it; the final
    weights equal an uninterrupted run (≤1 step of work lost, replayed)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    worker = os.path.join(_ROOT, "tests", "ckpt_worker.py")

    # uninterrupted reference
    ref_out = str(tmp_path / "ref.npy")
    subprocess.run([sys.executable, worker, str(tmp_path / "ck_ref"), "8",
                    "-1", ref_out], env=env, check=True, timeout=300,
                   capture_output=True, text=True)

    # crashing run under the autoresume supervisor
    crash_out = str(tmp_path / "crash.npy")
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "autoresume.py"),
         "--max-restarts", "2", "--",
         sys.executable, worker, str(tmp_path / "ck_crash"), "8", "5",
         crash_out],
        env=env, timeout=600, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "restarting" in proc.stderr
    onp.testing.assert_array_equal(onp.load(ref_out), onp.load(crash_out))


def test_async_snapshot_isolated_from_later_steps(tmp_path):
    """The on-device snapshot really decouples the save from the step
    loop: keep training IMMEDIATELY after an async save() and the
    checkpoint must still hold the state as of save time, not the
    mutated buffers."""
    net, trainer = _make()
    _train_steps(net, trainer, 3)
    w_at_save = net.weight.data().asnumpy()
    nu_at_save = trainer._optimizer.num_update
    with CheckpointManager(str(tmp_path), async_save=True) as mgr:
        mgr.save(3, net=net, trainer=trainer)
        _train_steps(net, trainer, 4, start=4)  # mutates params + state
    net2, trainer2 = _make(seed=9)
    info = CheckpointManager(str(tmp_path)).restore(net=net2,
                                                    trainer=trainer2)
    assert info["step"] == 3
    onp.testing.assert_array_equal(net2.weight.data().asnumpy(), w_at_save)
    assert trainer2._optimizer.num_update == nu_at_save


def _saved_two_steps(tmp_path):
    net, trainer = _make()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    for s in (1, 2):
        _train_steps(net, trainer, 1, start=s)
        mgr.save(s, net=net, trainer=trainer)
    return net, trainer, mgr


def _step_file(mgr, step, name):
    return os.path.join(mgr._step_dir(step), name)


def test_restore_skips_truncated_array_file(tmp_path):
    net, trainer, mgr = _saved_two_steps(tmp_path)
    path = _step_file(mgr, 2, "arrays-proc0")
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 8)
    assert mgr.all_steps() == [1]  # size-vs-manifest check demotes step 2
    net2, trainer2 = _make(seed=9)
    with pytest.warns(RuntimeWarning, match="incomplete"):
        info = mgr.restore(net=net2, trainer=trainer2)
    assert info["step"] == 1


def test_restore_skips_missing_manifest(tmp_path):
    net, trainer, mgr = _saved_two_steps(tmp_path)
    os.remove(_step_file(mgr, 2, "manifest-proc0.json"))
    assert mgr.all_steps() == [1]  # format-2 dir without manifest
    with pytest.warns(RuntimeWarning, match="incomplete"):
        info = mgr.restore(net=_make(seed=9)[0])
    assert info["step"] == 1


def test_restore_skips_checksum_mismatch(tmp_path):
    """Silent corruption (size unchanged, bytes flipped) passes the
    cheap completeness check but fails restore-time CRC validation —
    skipped with a warning, previous step restored."""
    net, trainer, mgr = _saved_two_steps(tmp_path)
    path = _step_file(mgr, 2, "arrays-proc0")
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size - 40)
        b = f.read(1)
        f.seek(size - 40)
        f.write(bytes([b[0] ^ 0xFF]))
    assert mgr.all_steps() == [1, 2]  # completeness can't see bit rot
    net2, trainer2 = _make(seed=9)
    with pytest.warns(RuntimeWarning, match="falling back"):
        info = mgr.restore(net=net2, trainer=trainer2)
    assert info["step"] == 1
    with pytest.raises(CheckpointCorrupt):
        mgr.restore(step=2, net=net2)  # a pinned corrupt step RAISES


def test_restore_skips_partially_renamed_tmp_dir(tmp_path):
    """Crash mid-commit: some shard files renamed into the final dir but
    no meta.json yet, plus a leftover tmp dir.  Restore warns and falls
    back; a fresh manager sweeps this process's stale tmp dirs."""
    import shutil

    net, trainer, mgr = _saved_two_steps(tmp_path)
    partial = mgr._step_dir(3)
    os.makedirs(partial)
    shutil.copy(_step_file(mgr, 2, "state-proc0.pkl"),
                os.path.join(partial, "state-proc0.pkl"))
    tmp_left = mgr._step_dir(4) + ".tmp-0"
    os.makedirs(tmp_left)
    with open(os.path.join(tmp_left, "junk"), "w") as f:
        f.write("x")
    with pytest.warns(RuntimeWarning, match="incomplete"):
        info = mgr.restore(net=_make(seed=9)[0])
    assert info["step"] == 2
    CheckpointManager(str(tmp_path))  # constructor sweeps stale tmp dirs
    assert not os.path.exists(tmp_left)
    assert os.path.exists(partial)  # partial FINAL dirs are kept (evidence)


def test_prune_never_deletes_inflight_step(tmp_path):
    """A committed step whose write is (still) marked in flight must
    survive pruning — out-of-order async commits would otherwise let a
    newer save evict a step the worker is mid-write on."""
    net, trainer = _make()
    mgr = CheckpointManager(str(tmp_path), keep=1, async_save=False)
    for s in (1, 2):
        _train_steps(net, trainer, 1, start=s)
        mgr.save(s, net=net, trainer=trainer)
    assert mgr.all_steps() == [2]  # keep=1 pruned step 1
    with mgr._inflight_lock:
        mgr._inflight.add(2)
    _train_steps(net, trainer, 1, start=3)
    mgr.save(3, net=net, trainer=trainer)
    assert mgr.all_steps() == [2, 3]  # 2 was due for eviction but inflight
    with mgr._inflight_lock:
        mgr._inflight.discard(2)
    _train_steps(net, trainer, 1, start=4)
    mgr.save(4, net=net, trainer=trainer)
    assert mgr.all_steps() == [4]


def test_write_retries_transient_failures(tmp_path, monkeypatch):
    """Transient OSErrors retry with backoff; a hard failure surfaces
    on wait()/close() after the budget."""
    from incubator_mxnet_tpu.utils import serialization

    net, trainer = _make()
    real = serialization.save_ndarrays
    fails = {"n": 2}

    def flaky(path, arrays):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("simulated transient write failure")
        return real(path, arrays)

    monkeypatch.setattr(serialization, "save_ndarrays", flaky)
    mgr = CheckpointManager(str(tmp_path), async_save=True, retries=3,
                            retry_backoff=0.01)
    mgr.save(1, net=net, trainer=trainer)
    mgr.close()
    assert mgr.all_steps() == [1]
    assert fails["n"] == 0

    monkeypatch.setattr(
        serialization, "save_ndarrays",
        lambda *a, **k: (_ for _ in ()).throw(OSError("disk gone")))
    mgr2 = CheckpointManager(str(tmp_path / "hard"), async_save=True,
                             retries=1, retry_backoff=0.01)
    mgr2.save(1, net=net, trainer=trainer)
    with pytest.raises(OSError, match="disk gone"):
        mgr2.close()


def test_async_save_telemetry(tmp_path):
    """The async path reports stall/write/bytes telemetry, and the
    caller-visible stall is far below the full write time."""
    telemetry.enable()
    telemetry.get_registry().clear()
    try:
        net, trainer = _make()
        _train_steps(net, trainer, 1)
        with CheckpointManager(str(tmp_path), async_save=True) as mgr:
            for s in (1, 2, 3):
                mgr.save(s, net=net, trainer=trainer)
        stall = telemetry.histogram("checkpoint_step_stall_seconds")
        write = telemetry.histogram("checkpoint_write_seconds")
        assert stall.count == 3
        assert write.count == 3
        assert telemetry.counter("checkpoint_bytes_total").value > 0
    finally:
        telemetry.get_registry().clear()
        telemetry.disable()


class _ResizeMLP(HybridBlock):
    """Tiny MLP with param sizes (30, 5, 15, 3) not all divisible by
    either mesh size — exercises re-flat-pad on BOTH D=8 and D=4."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.fc1 = nn.Dense(5, in_units=6, activation="tanh")
        self.fc2 = nn.Dense(3, in_units=5)

    def forward(self, x, y):
        pred = self.fc2(self.fc1(x))
        return ((pred - y) ** 2).mean()


def _make_mesh_mlp(mesh, seed=0):
    mx.random.seed(seed)
    model = _ResizeMLP()
    model.initialize()
    model(NDArray(jnp.ones((8, 6))), NDArray(jnp.ones((8, 3))))
    model.hybridize()
    trainer = Trainer(model.collect_params(), "sgd",
                      {"learning_rate": 0.05, "momentum": 0.9}, mesh=mesh)
    return model, trainer


def _train_mesh_steps(model, trainer, mesh, n, start=1):
    losses = []
    for step in range(start, start + n):
        key = jax.random.PRNGKey(2000 + step)
        kx, ky = jax.random.split(key)
        x = shard_batch(jax.random.normal(kx, (8, 6)), mesh)
        y = shard_batch(jax.random.normal(ky, (8, 3)), mesh)
        with autograd.record():
            loss = model(x, y)
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    return losses


def test_mesh_resize_restore_8_to_4(tmp_path, mesh8):
    """Elastic resume: ZeRO-1 state saved on data=8 restores onto a
    data=4 mesh — re-flat-padded and re-sliced shard-local — and the
    continued loss curve matches the uninterrupted data=8 run."""
    model, trainer = _make_mesh_mlp(mesh8)
    _train_mesh_steps(model, trainer, mesh8, 3)
    trainer.flush()
    assert trainer._zero_sig() == ("explicit", "data", 8)
    assert any(isinstance(s, zero_mod.Zero1State)
               for s in trainer._states.values())
    momentum_at_save = trainer.host_states()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, net=model, trainer=trainer)

    mesh4 = par.create_mesh(data=4)
    model2, trainer2 = _make_mesh_mlp(mesh4, seed=9)
    info = mgr.restore(net=model2, trainer=trainer2)
    assert info["step"] == 3
    assert trainer2._zero_sig() == ("explicit", "data", 4)
    # state eagerly re-adopted onto the NEW data axis, shard-local
    zs = [s for s in trainer2._states.values()
          if isinstance(s, zero_mod.Zero1State)]
    assert zs and all(z.meta.D == 4 for z in zs)
    for k, st in trainer2._states.items():
        want = momentum_at_save[k]
        got = zero_mod.host_canonical(st) \
            if isinstance(st, zero_mod.Zero1State) else st
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        rtol=1e-6, atol=1e-7)
    # loss-curve continuity: resized resume tracks the uninterrupted run
    ref = _train_mesh_steps(model, trainer, mesh8, 2, start=4)
    got = _train_mesh_steps(model2, trainer2, mesh4, 2, start=4)
    onp.testing.assert_allclose(ref, got, rtol=2e-4, atol=2e-5)
    p_ref = {n: onp.asarray(jax.device_get(p.data()._data))
             for n, p in model._collect_params_with_prefix().items()}
    p_got = {n: onp.asarray(jax.device_get(p.data()._data))
             for n, p in model2._collect_params_with_prefix().items()}
    for n in p_ref:
        onp.testing.assert_allclose(p_ref[n], p_got[n], err_msg=n,
                                    rtol=2e-3, atol=1e-4)


def test_zero_reshard_roundtrip(mesh8):
    """gluon.zero.reshard: D=8 → D=4 → canonical equals the original
    canonical (pure re-flat-pad + re-slice, no value drift)."""
    import math

    mesh4 = par.create_mesh(data=4)
    state = {"mom": jnp.arange(23, dtype=jnp.float32)}  # 23 % 8 != 0
    w = jnp.zeros((23,), jnp.float32)
    z8 = zero_mod.adopt(state, w, 8, mesh8, "data", mp=False)
    z4 = zero_mod.reshard(z8, 4, mesh4, "data")
    assert z4.meta.D == 4
    assert z4.meta.npad == -(-23 // 4) * 4
    onp.testing.assert_array_equal(
        onp.asarray(zero_mod.canonical(z4)["mom"]),
        onp.asarray(state["mom"]))
    assert zero_mod.reshard(z4, 4, mesh4, "data") is z4  # same-D no-op


def test_autoresume_heartbeat_kills_hung_job(tmp_path):
    """A job that stops heartbeating is detected and killed (the
    barrier-timeout failure mode), then the restart budget applies."""
    hb = str(tmp_path / "hb")
    hang = str(tmp_path / "hang.py")
    with open(hang, "w") as f:
        f.write(
            "import sys, time\n"
            f"open({hb!r}, 'w').write('x')\n"
            "time.sleep(600)\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "autoresume.py"),
         "--max-restarts", "0", "--heartbeat-file", hb,
         "--heartbeat-timeout", "2", "--poll-interval", "0.2", "--",
         sys.executable, hang],
        timeout=120, capture_output=True, text=True)
    assert proc.returncode != 0
    assert "heartbeat stale" in proc.stderr
