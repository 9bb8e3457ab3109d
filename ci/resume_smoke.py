"""CI gate: kill-and-resume on a RESIZED mesh really works (ISSUE 11).

The end-to-end preemption story, exercised with a real subprocess and a
real SIGTERM (same idiom as ci/flight_recorder_smoke.py):

1. REFERENCE: a child trains N steps uninterrupted on a data=8 mesh
   (ZeRO-1 explicit tier), async-checkpointing every step, and records
   its loss curve + final params.
2. KILL: a second child trains the same schedule but parks after step K
   (once the async worker has committed at least step K-2) with
   ``MXTPU_FLIGHT_DIR`` set; the parent SIGTERMs it and asserts the
   SIGTERM death code, a parseable flight bundle with reason
   ``signal:SIGTERM``, and a committed (manifest-complete) checkpoint
   no older than K-2 — WITHOUT importing jax in the parent: manifest +
   meta files are plain JSON.
3. RESUME: a third child reuses the kill run's checkpoint dir on a
   data=4 mesh — half the data axis, as after losing half the pod.
   Restore must fall back past any write the SIGTERM truncated,
   re-shard the ZeRO-1 state onto D=4 (``Zero1State.meta.D == 4``),
   and train to N.  The parent then pins:
   * loss-curve continuity: the resumed per-step losses match the
     uninterrupted reference on every overlapping step (rtol 2e-3 —
     the dryrun's zero-vs-replicated parity bound is 2e-4, and the
     resize adds one more reduction-order change);
   * final params match the reference within the same tolerance;
   * the ASYNC save stalls the step loop < 10% of a measured
     synchronous save of the same state (median stall from
     ``checkpoint_step_stall_seconds`` vs median of 3 sync saves).

Run via ci/lint.sh (and the multichip dryrun); standalone:
    JAX_PLATFORMS=cpu python ci/resume_smoke.py
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

STEPS = 10        # reference/uninterrupted length
PARK_AFTER = 5    # kill run parks (and is SIGTERMed) after this step
BATCH = 16        # divisible by both mesh sizes (8 and 4)
D_IN, D_HID = 512, 2048


# -- child ---------------------------------------------------------------- #
def _build():
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    class MLPWithLoss(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.fc1 = nn.Dense(D_HID, in_units=D_IN, activation="tanh")
            self.fc2 = nn.Dense(D_IN, in_units=D_HID)

        def forward(self, x, y):
            return ((self.fc2(self.fc1(x)) - y) ** 2).mean()

    mx.random.seed(0)
    model = MLPWithLoss()
    model.initialize()
    model(NDArray(jnp.ones((BATCH, D_IN))), NDArray(jnp.ones((BATCH, D_IN))))
    model.hybridize()
    return model


def _batch(step):
    import jax
    import jax.numpy as jnp

    kx, ky = jax.random.split(jax.random.PRNGKey(step))
    return (jax.random.normal(kx, (BATCH, D_IN), jnp.float32),
            jax.random.normal(ky, (BATCH, D_IN), jnp.float32))


def child(args):
    import jax
    import numpy as onp

    import incubator_mxnet_tpu.parallel as par
    from incubator_mxnet_tpu import autograd, telemetry
    from incubator_mxnet_tpu.gluon import Trainer
    from incubator_mxnet_tpu.gluon import zero as zero_mod
    from incubator_mxnet_tpu.gluon.utils import shard_batch
    from incubator_mxnet_tpu.utils.checkpoint import CheckpointManager

    telemetry.enable()
    mesh = par.create_mesh(data=args.mesh)
    model = _build()
    trainer = Trainer(model.collect_params(), "sgd",
                      {"learning_rate": 0.01, "momentum": 0.9}, mesh=mesh)
    # queue depth covers the whole run: the gate measures the protocol's
    # intrinsic stall (snapshot dispatch + enqueue), not back-pressure
    mgr = CheckpointManager(args.ckpt_dir, keep=3, async_save=True,
                            queue_depth=STEPS + 2)
    start = 0
    if mgr.latest_step() is not None:
        import warnings

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            info = mgr.restore(net=model, trainer=trainer)
        start = info["step"]
        for w in caught:
            print(f"RESTORE-WARN {w.message}", flush=True)
        print(f"RESUMED {start}", flush=True)

    losses = {}
    for step in range(start + 1, args.steps + 1):
        x, y = _batch(step)
        with autograd.record():
            loss = model(shard_batch(x, mesh), shard_batch(y, mesh))
        loss.backward()
        trainer.step(1)
        mgr.save(step, net=model, trainer=trainer)
        losses[step] = float(loss.asnumpy())
        print(f"STEP {step} {losses[step]:.6f}", flush=True)
        if args.park_after and step >= args.park_after:
            # park only once the worker has committed step-K-2 — the
            # parent's SIGTERM may still truncate the later writes
            # (restore's fallback path covers those)
            deadline = time.time() + 120
            while time.time() < deadline:
                latest = mgr.latest_step()
                if latest is not None and latest >= step - 2:
                    break
                time.sleep(0.05)
            print("PARKED", flush=True)
            while True:
                time.sleep(0.1)

    mgr.close()
    stall_p50 = telemetry.histogram(
        "checkpoint_step_stall_seconds").percentile(0.5)
    # measured synchronous baseline: same full state, inline fetch+write
    sync_times = []
    for i in range(3):
        sdir = tempfile.mkdtemp(prefix="mxtpu_sync_ckpt_")
        smgr = CheckpointManager(sdir, async_save=False)
        t0 = time.perf_counter()
        smgr.save(10_000 + i, net=model, trainer=trainer)
        sync_times.append(time.perf_counter() - t0)
        import shutil

        shutil.rmtree(sdir, ignore_errors=True)
    zero_D = 0
    for st in trainer._states.values():
        if isinstance(st, zero_mod.Zero1State):
            zero_D = st.meta.D
            break
    params = onp.concatenate(
        [onp.asarray(jax.device_get(p.data()._data)).ravel()
         for _n, p in sorted(model._collect_params_with_prefix().items())])
    onp.savez(args.out,
              steps=onp.asarray(sorted(losses)),
              losses=onp.asarray([losses[s] for s in sorted(losses)]),
              params=params,
              stall_p50=stall_p50,
              sync_save_seconds=sorted(sync_times)[1],
              resumed_from=start,
              zero_D=zero_D)
    print(f"DONE start={start} zero_D={zero_D} stall_p50={stall_p50:.4f}s "
          f"sync={sorted(sync_times)[1]:.4f}s", flush=True)


# -- parent --------------------------------------------------------------- #
def _child_env(flight_dir=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8")
    if flight_dir is not None:
        env["MXTPU_FLIGHT_DIR"] = flight_dir
    return env


def _run_child(extra, env, timeout=600):
    cmd = [sys.executable, os.path.abspath(__file__), "--child"] + extra
    proc = subprocess.run(cmd, env=env, timeout=timeout,
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise AssertionError(
            f"child {extra} failed rc={proc.returncode}:\n"
            f"{proc.stdout[-4000:]}\n{proc.stderr[-4000:]}")
    return proc


def _complete_steps(ckpt_dir):
    """Committed steps by manifest+meta inspection — pure JSON, no jax
    import in the parent process."""
    steps = []
    for name in sorted(os.listdir(ckpt_dir)):
        d = os.path.join(ckpt_dir, name)
        if not name.startswith("ckpt-") or ".tmp" in name:
            continue
        try:
            with open(os.path.join(d, "meta.json")) as f:
                meta = json.load(f)
            with open(os.path.join(d, "manifest-proc0.json")) as f:
                man = json.load(f)
        except (OSError, ValueError):
            continue
        if all(os.path.getsize(os.path.join(d, fn)) == rec["bytes"]
               for fn, rec in man["files"].items()
               if os.path.exists(os.path.join(d, fn))) \
                and all(os.path.exists(os.path.join(d, fn))
                        for fn in man["files"]):
            steps.append(meta["step"])
    return sorted(steps)


def main():
    import numpy as onp

    root = tempfile.mkdtemp(prefix="mxtpu_resume_smoke_")
    flight_dir = os.path.join(root, "flight")
    ckpt_ref = os.path.join(root, "ck_ref")
    ckpt_elastic = os.path.join(root, "ck_elastic")
    ref_out = os.path.join(root, "ref.npz")
    res_out = os.path.join(root, "res.npz")

    # 1. uninterrupted reference on data=8
    _run_child(["--mesh", "8", "--steps", str(STEPS),
                "--ckpt-dir", ckpt_ref, "--out", ref_out], _child_env())

    # 2. kill run: park after step K, SIGTERM from here
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--mesh", "8", "--steps", str(STEPS),
         "--park-after", str(PARK_AFTER),
         "--ckpt-dir", ckpt_elastic, "--out", os.path.join(root, "x.npz")],
        env=_child_env(flight_dir), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 300
        line = ""
        while time.time() < deadline:
            line = proc.stdout.readline()
            if "PARKED" in line:
                break
            if proc.poll() is not None:
                raise AssertionError(
                    f"kill-run child died early: {line}{proc.stdout.read()}")
        else:
            raise AssertionError("kill-run child never parked")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == -signal.SIGTERM or rc == 128 + signal.SIGTERM, \
        f"kill-run exit code {rc}, wanted SIGTERM death (-15 or 143)"

    # flight bundle shipped
    jsonl = os.path.join(flight_dir, "flight.jsonl")
    assert os.path.exists(jsonl), f"no flight.jsonl in {flight_dir}"
    with open(jsonl) as f:
        lines = [json.loads(l) for l in f if l.strip()]
    assert lines and lines[0]["flight_meta"]["reason"] == "signal:SIGTERM", \
        f"flight bundle wrong: {lines[:1]}"

    # a committed checkpoint no older than K-2 survived the SIGTERM
    committed = _complete_steps(ckpt_elastic)
    assert committed and committed[-1] >= PARK_AFTER - 2, \
        f"latest committed step {committed} < {PARK_AFTER - 2}"

    # 3. resume on HALF the data axis
    res = _run_child(["--mesh", "4", "--steps", str(STEPS),
                      "--ckpt-dir", ckpt_elastic, "--out", res_out],
                     _child_env())
    assert "RESUMED" in res.stdout, res.stdout[-2000:]

    ref = onp.load(ref_out)
    got = onp.load(res_out)
    assert int(got["zero_D"]) == 4, \
        f"resumed state not re-sharded to D=4: {got['zero_D']}"
    assert int(got["resumed_from"]) >= PARK_AFTER - 2

    # loss-curve continuity on every overlapping step
    ref_by_step = dict(zip(ref["steps"].tolist(), ref["losses"].tolist()))
    got_by_step = dict(zip(got["steps"].tolist(), got["losses"].tolist()))
    assert got_by_step, "resume run trained no steps"
    for s, v in got_by_step.items():
        onp.testing.assert_allclose(
            v, ref_by_step[s], rtol=2e-3,
            err_msg=f"loss diverged at step {s} after resized resume")
    onp.testing.assert_allclose(got["params"], ref["params"],
                                rtol=2e-3, atol=1e-4,
                                err_msg="final params diverged")

    # async protocol stalls the step loop < 10% of a synchronous save
    stall, sync = float(got["stall_p50"]), float(got["sync_save_seconds"])
    assert stall < 0.10 * sync, \
        (f"async save stall p50 {stall * 1e3:.1f}ms is not < 10% of the "
         f"synchronous write {sync * 1e3:.1f}ms")

    print(f"resume smoke: OK (killed after step {PARK_AFTER}, committed "
          f"{committed[-1]}, resumed from {int(got['resumed_from'])} on "
          f"data=4, {len(got_by_step)} continuity steps, stall p50 "
          f"{stall * 1e3:.2f}ms vs sync {sync * 1e3:.1f}ms)")


if __name__ == "__main__":
    if "--child" in sys.argv:
        p = argparse.ArgumentParser()
        p.add_argument("--child", action="store_true")
        p.add_argument("--mesh", type=int, required=True)
        p.add_argument("--steps", type=int, required=True)
        p.add_argument("--park-after", type=int, default=0)
        p.add_argument("--ckpt-dir", required=True)
        p.add_argument("--out", required=True)
        child(p.parse_args())
    else:
        main()
