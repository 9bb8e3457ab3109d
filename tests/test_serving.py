"""Continuous-batching serving engine (`serving/`): the robustness
envelope (ISSUE 12).

The load-bearing contracts:

* **Greedy parity** — engine output token-for-token equals
  `lm_generate` (the paged decode re-implements the cached step
  against a shared pool; parity pins its numerics).
* **Eviction bit-identity** — cancelling/timing-out one sequence
  mid-batch leaves survivors' outputs byte-identical to an unperturbed
  run (lanes are independent; masked scratch reads contribute exactly
  0.0), and the freed blocks are reused by a later admission.
* **Overload safety** — a full queue SHEDS (counted, no deadlock), SLO
  estimates shed late requests, deadlines evict mid-batch,
  abandoned streams release their KV blocks, close() joins the
  scheduler thread, and scheduler errors are parked and re-raised.

Everything runs tiny nets, small token counts and 1 ms polls: the
tier-1 870 s budget is nearly saturated, so shared module-scope
engines keep the compile count at a handful.
"""
import threading
import time

import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.models.generation import lm_generate, lm_stream
from incubator_mxnet_tpu.models.transformer import TransformerLM
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.serving import (BlockPool, RequestCancelled,
                                         RequestFailed, RequestShed,
                                         RequestTimedOut, ServingEngine)

V, C, DFF, L, H, MAXLEN = 61, 16, 32, 1, 2, 64
P1 = onp.array([3, 7, 11, 2, 9], onp.int32)
P2 = onp.array([5, 1, 2], onp.int32)
_POLL = 0.001


def _wait(pred, timeout=30.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(0.002)
    return False


def _slow_step(seconds):
    def hook(phase):
        if phase == "step":
            time.sleep(seconds)
    return hook


@pytest.fixture(scope="module")
def net():
    mx.random.seed(0)
    n = TransformerLM(vocab=V, units=C, hidden_size=DFF, num_layers=L,
                      num_heads=H, max_len=MAXLEN, dropout=0.0)
    n.initialize()
    n(NDArray(jnp.ones((1, 4), jnp.int32)))
    return n


@pytest.fixture(scope="module")
def engine(net):
    """The shared float engine: one compiled step program + a couple of
    prefill buckets for the whole module."""
    eng = ServingEngine(net, max_batch=2, block_size=8,
                        poll_interval=_POLL)
    yield eng
    try:
        eng.close()
    except Exception:
        pass


@pytest.fixture
def clean_engine(engine):
    """The shared engine with hooks/budgets reset before AND after."""
    engine.set_fault_hook(None)
    engine.set_ttft_budget(None)
    yield engine
    engine.drain(timeout=30)
    engine.set_fault_hook(None)
    engine.set_ttft_budget(None)


# --------------------------------------------------------------------- #
# block pool accounting
# --------------------------------------------------------------------- #
def test_block_pool_deterministic_and_guarded():
    pool = BlockPool(6)                    # scratch + 5 usable
    assert pool.num_free == 5
    a = pool.alloc(3)
    assert a == [1, 2, 3]                  # lowest-first, deterministic
    assert pool.alloc(3) is None           # all-or-nothing
    pool.free([2])
    assert pool.alloc(1) == [2]            # freed id reused first
    with pytest.raises(ValueError):
        pool.free([2, 2])                  # double free
    with pytest.raises(ValueError):
        pool.free([0])                     # scratch is not freeable
    with pytest.raises(ValueError):
        BlockPool(1)


# --------------------------------------------------------------------- #
# parity + streaming
# --------------------------------------------------------------------- #
def test_greedy_parity_with_lm_generate(net, clean_engine):
    ref = onp.asarray(lm_generate(net, P1[None, :], 8))[0, len(P1):]
    got = clean_engine.submit(P1, 8).result(timeout=60)
    assert got == ref.tolist()
    # co-batched with a second request: both still exact
    r1 = clean_engine.submit(P1, 8)
    r2 = clean_engine.submit(P2, 6)
    ref2 = onp.asarray(lm_generate(net, P2[None, :], 6))[0, len(P2):]
    assert r1.result(timeout=60) == ref.tolist()
    assert r2.result(timeout=60) == ref2.tolist()


def test_lm_stream_yields_and_finishes(net, clean_engine):
    # N=8 reuses the parity test's reference program (per-net LRU)
    ref = onp.asarray(lm_generate(net, P1[None, :], 8))[0, len(P1):]
    toks = list(lm_stream(net, P1, 8, engine=clean_engine))
    assert toks == ref.tolist()


def test_eos_and_single_token_retire(net, clean_engine):
    full = onp.asarray(lm_generate(net, P1[None, :], 8))[0, len(P1):]
    # max_new=1: the prefill emits the only token, no decode step runs;
    # greedy prefix property: it equals token 0 of the longer reference
    assert clean_engine.submit(P1, 1).result(timeout=60) == [int(full[0])]
    # eos freezes a sequence at the first eos token (host-side retire)
    eos = int(full[0])
    old = clean_engine._eos
    clean_engine._eos = eos
    try:
        assert clean_engine.submit(P1, 8).result(timeout=60) == [eos]
    finally:
        clean_engine._eos = old


# --------------------------------------------------------------------- #
# eviction correctness (the acceptance-criterion pair)
# --------------------------------------------------------------------- #
def test_mid_batch_eviction_leaves_survivor_bit_identical(clean_engine):
    eng = clean_engine
    # run A: unperturbed co-batch
    ra = eng.submit(P1, 10)
    rb = eng.submit(P2, 10)
    base = ra.result(timeout=60)
    rb.result(timeout=60)
    assert eng.drain(timeout=30)
    # run B: same submissions (allocator state reset => identical block
    # layout), neighbour cancelled mid-generation
    eng.set_fault_hook(_slow_step(0.02))   # widen the cancel window
    ra = eng.submit(P1, 10)
    rb = eng.submit(P2, 10)
    assert _wait(lambda: len(rb.tokens) >= 3)
    rb.cancel()
    assert ra.result(timeout=60) == base
    with pytest.raises(RequestCancelled):
        rb.result(timeout=60)
    eng.set_fault_hook(None)
    # run C: solo — scratch-block garbage from the neighbour never
    # reaches the survivor (masked positions contribute exactly 0)
    assert eng.submit(P1, 10).result(timeout=60) == base


def test_evicted_blocks_are_reused(clean_engine):
    eng = clean_engine
    eng.set_fault_hook(_slow_step(0.02))
    r1 = eng.submit(P1, 20)
    assert _wait(lambda: r1.status == "running")
    held = set(r1.block_ids)
    assert held
    r1.cancel()
    with pytest.raises(RequestCancelled):
        r1.result(timeout=30)
    eng.set_fault_hook(None)
    r3 = eng.submit(P2, 6)
    r3.result(timeout=60)
    assert set(r3.block_ids) & held       # freed blocks re-allocated
    st = eng.stats()
    assert st["blocks_free"] == st["blocks_total"]
    assert st["evicted"].get("cancel", 0) >= 1


def test_deadline_evicts_mid_batch(clean_engine):
    eng = clean_engine
    eng.set_fault_hook(_slow_step(0.02))
    req = eng.submit(P1, 50, deadline=0.08)
    with pytest.raises(RequestTimedOut):
        req.result(timeout=30)
    assert req.status == "evicted"
    assert 0 < len(req.tokens) < 50       # partial progress, then evicted
    st = eng.stats()
    assert st["evicted"].get("timeout", 0) >= 1


# --------------------------------------------------------------------- #
# the host's share of an iteration
# --------------------------------------------------------------------- #
def test_a_waiting_backlog_is_walked_only_when_it_may_hold_one_to_reap(net):
    """Cancellation and deadlines apply to queued requests, but a backlog
    without either is not looked through every iteration: the flag is up
    while the queue may hold a request to reap, and down again after."""
    eng = ServingEngine(net, max_batch=1, block_size=8, poll_interval=_POLL,
                        fault_hook=_slow_step(0.01))
    try:
        head = eng.submit(P1, 40)
        plain = [eng.submit(P2, 2) for _ in range(3)]
        assert _wait(lambda: head.status == "running")
        assert not eng._queue_reap          # nothing queued can be reaped
        timed = eng.submit(P2, 2, deadline=0.02)
        with pytest.raises(RequestShed) as ei:
            timed.result(timeout=30)
        assert ei.value.reason == "deadline"
        assert _wait(lambda: not eng._queue_reap)   # looked at, then left
        plain[1].cancel()
        with pytest.raises(RequestCancelled):
            plain[1].result(timeout=30)
        head.cancel()
        assert plain[0].result(timeout=30) and plain[2].result(timeout=30)
        assert not eng._queue_reap
    finally:
        eng.close()


def test_a_last_chunk_is_committed_behind_the_step_it_shares_an_iteration_with(
        net):
    """A chunk and a decode step of one iteration: the step is handed to
    the device before the chunk's first token is fetched (its lanes were
    snapshotted before the chunk ran, so it does not need it), and the
    chunk is committed before the step is.  Tokens are what they were."""
    order = []

    def hook(phase):
        order.append((phase, len(second.tokens) if second else None))

    second = None
    eng = ServingEngine(net, max_batch=2, block_size=8, poll_interval=_POLL,
                        prefill_chunk=4, fault_hook=hook)
    try:
        first = eng.submit(P1, 30)
        assert _wait(lambda: len(first.tokens) >= 2)
        second = eng.submit(P2, 6)
        got = second.result(timeout=30)
        first.result(timeout=30)
    finally:
        eng.close()
    assert got == onp.asarray(
        lm_generate(net, P2[None, :], 6))[0, len(P2):].tolist()
    # the iteration that ran `second`'s only chunk: its "step" hook still
    # saw no token of it (the commit waits for the step's dispatch), the
    # next iteration's did
    i = max(k for k, (phase, n) in enumerate(order)
            if phase == "prefill" and n == 0)
    assert order[i + 1] == ("step", 0)
    assert [n for phase, n in order[i + 2:] if phase == "step"][0] >= 1


def test_host_arguments_travel_as_one_buffer():
    """`programs._HostPacked`: the numpy arguments of a served call are
    laid end to end in one int32 array and cut apart in the program; the
    results are those of the plain jitted function, the device arguments
    are donated, the compiled program keeps the function's name."""
    import jax

    from incubator_mxnet_tpu.serving.programs import _HostPacked

    def serving_toy(pool, table, flags, keys, start, params):
        picked = jnp.where(flags, table[:, 0], -1) + start
        return pool + params["w"].sum(), picked, keys[:, 1] >> 1

    packed = _HostPacked(serving_toy, 1)
    table = onp.arange(12, dtype=onp.int32).reshape(4, 3)
    flags = onp.array([True, False, True, True])
    keys = onp.array([[1, 0xFFFFFFFF]] * 4, onp.uint32)
    params = {"w": jnp.ones((2, 2))}
    args = (table, flags, keys, onp.int32(7), params)
    want = jax.jit(serving_toy)(jnp.zeros(3), *args)
    pool = jnp.zeros(3)
    got = packed(pool, *args)
    for g, w in zip(got, want):
        onp.testing.assert_array_equal(onp.asarray(g), onp.asarray(w))
    assert got[2].dtype == jnp.uint32 and pool.is_deleted()
    program = packed._jitted
    packed(jnp.zeros(3), *args)
    assert packed._jitted is program        # one program a signature
    hlo = packed.lower(jnp.zeros(3), *args).compile().as_text()
    assert "jit_serving_toy" in hlo
    entry = hlo[hlo.index("ENTRY"):]
    assert entry.count(" parameter(") == 3  # pool, ONE host buffer, w
    with pytest.raises(TypeError, match="4-byte"):
        packed(jnp.zeros(3), table.astype(onp.int64), flags, keys,
               onp.int32(7), params)


# --------------------------------------------------------------------- #
# overload: bounded queue, shedding, no deadlock
# --------------------------------------------------------------------- #
def test_queue_saturation_sheds_without_deadlock(net):
    eng = ServingEngine(net, max_batch=1, block_size=8, max_queue=2,
                        poll_interval=_POLL,
                        fault_hook=_slow_step(0.02))
    try:
        reqs = [eng.submit(P2, 6) for _ in range(8)]
        shed = [r for r in reqs if r.status == "shed"]
        assert shed                        # bounded queue sheds, not blocks
        for r in shed:
            with pytest.raises(RequestShed) as ei:
                r.result(timeout=5)
            assert ei.value.reason == "queue_full"
        assert eng.drain(timeout=60)       # the admitted ones all finish
        done = [r for r in reqs if r.status == "done"]
        assert len(done) + len(shed) == len(reqs)
        assert eng.stats()["shed"]["queue_full"] == len(shed)
        # blocking submit waits for space instead of shedding
        r = eng.submit(P2, 2, block=True, timeout=30)
        assert r.result(timeout=30)
    finally:
        eng.close()


def test_slo_budget_sheds_estimated_late_requests(clean_engine):
    eng = clean_engine
    # seed the prefill EWMA, then make the TTFT estimate impossible
    eng.submit(P2, 2).result(timeout=60)
    eng.set_fault_hook(_slow_step(0.05))
    occupants = [eng.submit(P1, 12), eng.submit(P2, 12)]  # fill lanes
    assert _wait(lambda: all(r.status == "running" for r in occupants))
    eng.set_ttft_budget(1e-4)              # after the lanes are taken
    late = eng.submit(P2, 4)
    with pytest.raises(RequestShed) as ei:
        late.result(timeout=30)
    assert ei.value.reason == "slo"
    eng.set_ttft_budget(None)
    eng.set_fault_hook(None)
    for r in occupants:
        r.result(timeout=60)


def test_abandoned_stream_releases_blocks(clean_engine):
    eng = clean_engine
    eng.set_fault_hook(_slow_step(0.02))
    req = eng.submit(P1, 30)
    it = req.stream()
    assert isinstance(next(it), int)
    it.close()                             # caller walks away mid-stream
    assert _wait(lambda: eng.stats()["blocks_free"]
                 == eng.stats()["blocks_total"])
    assert req.status == "cancelled"
    eng.set_fault_hook(None)


# --------------------------------------------------------------------- #
# lifecycle: drain/close semantics, error handoff
# --------------------------------------------------------------------- #
def test_close_joins_scheduler_and_rejects_new_work(net):
    eng = ServingEngine(net, max_batch=1, block_size=8,
                        poll_interval=_POLL)
    thread = eng._thread
    eng.close()
    assert not thread.is_alive()           # tpulint TPU012: joined
    with pytest.raises(RuntimeError):
        eng.submit(P2, 2)
    eng.close()                            # idempotent


def test_close_aborts_inflight_requests(net):
    eng = ServingEngine(net, max_batch=1, block_size=8, max_queue=4,
                        poll_interval=_POLL,
                        fault_hook=_slow_step(0.05))
    running = eng.submit(P1, 50)
    queued = eng.submit(P2, 50)
    assert _wait(lambda: running.status == "running")
    eng.close()
    for r in (running, queued):
        assert r.status in ("cancelled",)
        with pytest.raises(RequestCancelled):
            r.result(timeout=5)


def test_scheduler_error_is_parked_and_reraised(net):
    boom = RuntimeError("injected scheduler fault")

    def hook(phase):
        if phase == "step":
            raise boom

    eng = ServingEngine(net, max_batch=1, block_size=8,
                        poll_interval=_POLL, fault_hook=hook)
    req = eng.submit(P2, 8)
    with pytest.raises(RequestFailed):
        req.result(timeout=30)
    assert req.status == "failed"
    with pytest.raises(RequestFailed):     # dead engine refuses work
        eng.submit(P2, 2)
    with pytest.raises(RequestFailed) as ei:
        eng.close()
    assert ei.value.__cause__ is boom
    eng.close()                            # after the re-raise: clean


def test_submit_validation(clean_engine):
    with pytest.raises(ValueError):
        clean_engine.submit(onp.zeros((0,), onp.int32), 2)
    with pytest.raises(ValueError):
        clean_engine.submit(P1, 0)
    with pytest.raises(ValueError):
        clean_engine.submit(P1, MAXLEN)    # P + N > max_seq_len
    with pytest.raises(ValueError):
        ServingEngine(clean_engine._programs._net, max_batch=0)
    with pytest.raises(ValueError):
        ServingEngine(clean_engine._programs._net, block_size=12)  # not a pow2


def test_concurrent_submitters_are_thread_safe(net, clean_engine):
    ref = onp.asarray(lm_generate(net, P2[None, :], 4))[0, len(P2):]
    results = [None] * 6

    def worker(i):
        results[i] = clean_engine.submit(P2, 4,
                                         block=True,
                                         timeout=60).result(timeout=60)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(results))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert all(r == ref.tolist() for r in results)


# --------------------------------------------------------------------- #
# telemetry + int8 path
# --------------------------------------------------------------------- #
def test_serving_metrics_are_recorded(net):
    from incubator_mxnet_tpu import telemetry

    telemetry.enable()
    try:
        reg = telemetry.get_registry()
        eng = ServingEngine(net, max_batch=1, block_size=8, max_queue=1,
                            poll_interval=_POLL,
                            fault_hook=_slow_step(0.02))
        try:
            reqs = [eng.submit(P2, 4) for _ in range(4)]
            assert eng.drain(timeout=60)
            deadline = eng.submit(P1, 50, deadline=0.05)
            with pytest.raises(RequestTimedOut):
                deadline.result(timeout=30)
        finally:
            eng.close()
        assert reg.get("serving_admitted_total").value >= 2
        assert reg.get("serving_shed_total",
                       {"reason": "queue_full"}).value >= 1
        assert reg.get("serving_evicted_total",
                       {"reason": "timeout"}).value >= 1
        assert reg.get("serving_queue_depth") is not None
        assert reg.get("serving_batch_occupancy").value >= 1
        assert reg.get("serving_kv_blocks_in_use") is not None
        ttft = reg.get("serving_ttft_seconds", {"path": "float"})
        tpot = reg.get("serving_tpot_seconds", {"path": "float"})
        assert ttft.snapshot()["count"] >= 1
        assert tpot.snapshot()["count"] >= 1
        # serving-path labels on the existing decode SLO gauges
        assert reg.get("decode_ttft_seconds",
                       {"path": "serving_float"}).value > 0
        del reqs
    finally:
        telemetry.disable()
        telemetry.get_registry().reset()


def test_int8_engine_matches_quantized_lm_generate():
    mx.random.seed(0)
    net = TransformerLM(vocab=V, units=C, hidden_size=DFF, num_layers=L,
                        num_heads=H, max_len=MAXLEN, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 4), jnp.int32)))
    net.cast("bfloat16")
    net.quantize_for_decode(act_quant="none")
    ref = onp.asarray(lm_generate(net, P1[None, :], 8))[0, len(P1):]
    with net.serve(max_batch=2, block_size=8,
                   poll_interval=_POLL) as eng:
        assert eng._path == "int8"
        assert eng.submit(P1, 8).result(timeout=60) == ref.tolist()
        # serve() caches and reuses the engine for equal config
        assert net.serve() is eng


# --------------------------------------------------------------------- #
# the seam: the engine schedules, PagedPrograms holds the device (ISSUE 33)
# --------------------------------------------------------------------- #
def test_engine_module_knows_no_device_library():
    """The scheduler's module binds nothing of JAX, of the kernels or of
    `models.generation`: arrows point engine -> programs -> ops, models."""
    import importlib

    mod = importlib.import_module("incubator_mxnet_tpu.serving.engine")
    assert not {"jnp", "jax", "pool_shapes", "pages_per_step", "G"} \
        & set(vars(mod))


def test_paged_programs_alone_serve_a_prompt(net, clean_engine):
    """`PagedPrograms` built by hand owns what its calls need: a chunk
    and a step give the engine's tokens, and it reports the engine's
    bytes."""
    from incubator_mxnet_tpu.serving import PagedPrograms
    from incubator_mxnet_tpu.serving.kv_pool import SCRATCH_BLOCK

    progs = PagedPrograms(net, max_batch=2, block_size=8, temperature=0.0,
                          top_k=0, quantized=None)
    try:
        assert progs.kv_pool_bytes == clean_engine.kv_pool_bytes > 0
        assert progs.state_bytes == clean_engine.state_bytes == 0
        assert progs.max_seq_len == clean_engine.max_seq_len
        nbps, P = progs.max_seq_len // 8, len(P1)
        row = onp.full((nbps,), SCRATCH_BLOCK, onp.int32)
        row[0] = 1                          # positions 0..7: prompt + a step
        toks = onp.zeros((progs.prefill_chunk_len,), onp.int32)
        toks[:P] = P1
        keys = onp.zeros((2, 2), onp.uint32)            # seed 0
        progs.gather_params()
        first = int(progs.prefill_chunk(row, toks, 0, P, keys[0], 0, P))
        tables = onp.full((2, nbps), SCRATCH_BLOCK, onp.int32)
        tables[0] = row
        nxt = progs.step(tables, onp.array([first, 0], onp.int32),
                         onp.array([P, 0], onp.int32),
                         onp.array([True, False]), keys, 1)
        want = clean_engine.submit(P1, 2, seed=0).result(timeout=60)
        assert [first, int(nxt[0])] == want
    finally:
        progs.release()
    assert progs.kv_pools == ((),) * 4 and progs.kv_pool_bytes > 0


def _device_arrays(obj):
    import jax

    return [x for x in jax.tree_util.tree_leaves(vars(obj))
            if isinstance(x, jax.Array)]


@pytest.mark.parametrize("flavour", ["int8_kv", "speculative"])
def test_a_closed_engine_holds_no_device_array(net, flavour):
    """`close()` leaves the programs without pools, scales, draft pools
    or gathered weights (the hybrid net's recurrent state:
    tests/test_hybrid_ssm.py); what they held is still reported."""
    kw = {"kv_dtype": "int8"}
    if flavour == "speculative":
        mx.random.seed(99)
        draft = TransformerLM(vocab=V, units=8, hidden_size=16,
                              num_layers=1, num_heads=1, max_len=MAXLEN,
                              dropout=0.0)
        draft.initialize()
        draft(NDArray(jnp.ones((1, 4), jnp.int32)))
        kw = {"speculate_k": 2, "draft_net": draft}
    eng = ServingEngine(net, max_batch=2, block_size=8, poll_interval=_POLL,
                        **kw)
    progs = eng._programs
    assert eng.submit(P1, 3).result(timeout=60)
    pool_k, pool_v, scale_k, scale_v = progs.kv_pools
    assert pool_k and pool_v and _device_arrays(progs)
    assert bool(scale_k and scale_v) == (flavour == "int8_kv")
    assert all(progs.draft_pools) == (flavour == "speculative")
    held = eng.kv_pool_bytes
    eng.close()
    assert _device_arrays(progs) == []
    assert progs.kv_pools == ((),) * 4 and progs.draft_pools == ((), ())
    assert progs.recurrent_state == () and progs._net is None
    assert eng.kv_pool_bytes == held > 0
    assert not any(hasattr(eng, a) for a in (
        "_pool_k", "_pool_v", "_scale_k", "_scale_v", "_rec", "_dpool_k",
        "_dpool_v"))
