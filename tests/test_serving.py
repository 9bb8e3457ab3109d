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
        # an eos met in a decode step is learnt one step late (the step
        # after is already handed over): that step's token is discarded
        j = next(j for j in range(2, 8) if full[j] not in full[:j])
        clean_engine._eos = int(full[j])
        req = clean_engine.submit(P1, 8)
        assert req.result(timeout=60) == full[:j + 1].tolist()
        assert clean_engine.drain(timeout=30)
        assert len(req.tokens) == len(req.t_tokens) == j + 1
        assert req.t_tokens[-1] <= req.t_done
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
    # the step in flight when the cancel was reaped ran the lane once
    # more: its token went nowhere
    assert eng.drain(timeout=30)
    assert len(rb.tokens) == len(rb.t_tokens) < 10
    assert rb.t_tokens[-1] <= rb.t_done
    # run C: solo — scratch-block garbage from the neighbour never
    # reaches the survivor (masked positions contribute exactly 0)
    assert eng.submit(P1, 10).result(timeout=60) == base


def test_evicted_blocks_are_reused(net, clean_engine):
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
    # the blocks' next holder reads nothing of what the evicted lane, or
    # the step that was in flight for it, wrote there
    assert r3.result(timeout=60) == onp.asarray(
        lm_generate(net, P2[None, :], 6))[0, len(P2):].tolist()
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
    chunk is committed behind the read of the step BEFORE that one (the
    loop runs one step ahead: the step handed over here is read in the
    next iteration).  Tokens are what they were."""
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
    assert [n for phase, n in order[i + 2:] if phase == "step"][0] == 1
    # and the first step that ran `second`'s lane was unread at the hook
    # after it: two hooks saw the chunk's one token
    assert [n for phase, n in order[i + 2:] if phase == "step"][:3] \
        == [1, 1, 2]


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
    # a device argument that is read and left (the step before's tokens):
    # a parameter of its own, not donated
    def serving_two(pool, prev, table, params):
        return pool + params["w"].sum(), prev + table[0, 0]

    kept = _HostPacked(serving_two, 2, 1)
    pool, prev = jnp.zeros(3), jnp.arange(4, dtype=jnp.int32)
    out = kept(pool, prev, table + 5, params)
    assert pool.is_deleted() and not prev.is_deleted()
    onp.testing.assert_array_equal(onp.asarray(out[1]), onp.arange(4) + 5)
    entry = kept.lower(jnp.zeros(3), prev, table, params).compile().as_text()
    # pool, the kept array, ONE host buffer, w
    assert entry[entry.index("ENTRY"):].count(" parameter(") == 4


# --------------------------------------------------------------------- #
# the decode loop runs one step ahead of its reads (ISSUE 35)
# --------------------------------------------------------------------- #
def _truncated(tokens, eos):
    """`tokens` up to and including the first `eos`."""
    return tokens[:tokens.index(eos) + 1] if eos in tokens else tokens


def test_a_step_is_handed_over_while_the_step_before_is_unread(net):
    """The "step" hook of step N+1 fires while step N's token is still
    undelivered, every step but a batch's first; `stats()["steps_ahead"]`
    and the ring's `ahead` count them; the tokens are `lm_generate`'s."""
    from incubator_mxnet_tpu.telemetry import profiler

    seen, box = [], []

    def hook(phase):
        if phase == "step" and box:
            seen.append(len(box[0].tokens))

    eng = ServingEngine(net, max_batch=2, block_size=8, poll_interval=_POLL,
                        fault_hook=hook)
    try:
        assert eng.varz_config()["steps_in_flight"] == 1
        since = time.monotonic()
        box.append(eng.submit(P1, 6))
        got = box[0].result(timeout=30)
        assert eng.drain(timeout=30)
        st = eng.stats()
        name = eng._name
    finally:
        eng.close()
    assert got == onp.asarray(
        lm_generate(net, P1[None, :], 6))[0, len(P1):].tolist()
    # step 1 saw the chunk's token; step 2 was handed over before step
    # 1's token was read, step 3 before step 2's, ...
    assert seen == [1, 1, 2, 3, 4]
    assert (st["steps"], st["steps_ahead"]) == (5, 4)
    records, _held = profiler.iterations(since, None, engine=name)
    assert [r.ahead for r in records] == [0, 1, 1, 1, 1]
    assert records[0].as_dict()["ahead"] == 0


@pytest.mark.parametrize("sampling", [{}, {"temperature": 0.9, "top_k": 7}],
                         ids=["greedy", "sampled"])
def test_tokens_are_lm_generates_one_step_ahead(net, sampling):
    """Five requests of staggered arrival through two lanes, greedy and
    sampled: every request's tokens are `lm_generate`'s for its seed,
    those that end on an EOS included (the lane ran one step more: that
    token is not delivered), a freed lane's blocks serve the next
    request, and the ledger's causes still sum to the wall."""
    mix = [(P1, 11), (P2, 12), (P1[::-1].copy(), 13), (P2 + 1, 14),
           ((P1 * 2) % V, 15)]
    plain = [onp.asarray(lm_generate(net, p[None, :], 10, seed=seed,
                                     **sampling))[0, len(p):].tolist()
             for p, seed in mix]
    # an EOS that ends the first request mid-way, by a decode step
    j = next(j for j in range(2, 10) if plain[0][j] not in plain[0][:j])
    eos = plain[0][j]
    # (the tokens of a shorter request are a prefix of the longer one's:
    # a pick depends on the seed and the position alone)
    counts = (10, 7, 10, 8, 4)
    want = [_truncated(t[:n], eos) for t, n in zip(plain, counts)]
    assert len(want[0]) == j + 1
    assert {w[-1] == eos for w in want} == {True, False}    # and by count
    eng = ServingEngine(net, max_batch=2, block_size=8, poll_interval=_POLL,
                        prefill_chunk=4, eos_id=eos, **sampling)
    try:
        handles = []
        for i, ((p, seed), n) in enumerate(zip(mix, counts)):
            handles.append(eng.submit(p, n, seed=seed))
            if i % 2:
                time.sleep(0.01)
        got = [h.result(timeout=60) for h in handles]
        assert eng.drain(timeout=30)
        st = eng.stats()
        violations = eng.profiler.invariant_violations
    finally:
        eng.close()
    assert got == want
    for h, w in zip(handles, want):
        assert len(h.tokens) == len(h.t_tokens) == len(w)
        assert h.status == "done" and h.t_tokens[-1] <= h.t_done
    early = set(handles[0].block_ids) | set(handles[1].block_ids)
    assert any(set(h.block_ids) & early for h in handles[2:])
    assert st["blocks_free"] == st["blocks_total"] and st["active"] == 0
    assert 0 < st["steps_ahead"] < st["steps"]
    assert violations == 0


def test_an_eos_one_step_before_a_neighbours_last_token(net):
    """Two lanes, the iterations pinned through the fault hook: request A
    meets its EOS in the step whose read falls one iteration before B's
    last token (by count).  The step handed over in between still runs
    A's lane; A gets no token from it, B gets its last, both are what
    `lm_generate` gives."""
    full = onp.asarray(lm_generate(net, P1[None, :], 8))[0, len(P1):].tolist()
    a = next(j for j in range(2, 8) if full[j] not in full[:j]) + 1
    both_in = threading.Event()

    def hook(phase):            # A's chunk waits until B is queued behind it
        if phase == "prefill":
            assert both_in.wait(30)

    eng = ServingEngine(net, max_batch=2, block_size=8, poll_interval=_POLL,
                        eos_id=full[a - 1], fault_hook=hook)
    try:
        # A's step s is handed over in iteration s+1 and read in s+2; B's
        # chunk runs one iteration after A's: B's a-th token (its last)
        # is read in iteration a+2, A's a-th (the EOS) in a+1
        ra = eng.submit(P1, 8)
        rb = eng.submit(P2, a)
        both_in.set()
        got_a, got_b = ra.result(timeout=30), rb.result(timeout=30)
        assert eng.drain(timeout=30)
        st = eng.stats()
    finally:
        eng.close()
    assert got_a == full[:a] and len(ra.tokens) == a
    assert got_b == onp.asarray(
        lm_generate(net, P2[None, :], a))[0, len(P2):].tolist()
    # a steps in all: B's a-1, the first of them A's second; A's lane ran
    # in the a-th too (its EOS was unread when that was handed over)
    assert st["steps"] == a and st["steps_ahead"] == a - 1
    assert ra.t_done <= rb.t_tokens[-1]
    assert st["blocks_free"] == st["blocks_total"]


@pytest.mark.parametrize("how", ["close", "drain", "cancel", "deadline"])
def test_no_token_is_lost_or_late_with_a_step_in_flight(net, how):
    """`close()`, `drain()`, a cancel and a deadline arrive while a step
    is handed over and unread (the scheduler sits in the next step's
    hook): no handle hangs, a step handed over before the request ended
    still delivers, none delivers after, and the ledger's causes sum to
    the wall."""
    handed, at_third, go_on = [], threading.Event(), threading.Event()

    def hook(phase):
        if phase == "step":
            handed.append(phase)
            if len(handed) == 3:        # step 2 is in flight, unread
                at_third.set()
                assert go_on.wait(30)

    eng = ServingEngine(net, max_batch=1, block_size=8, poll_interval=_POLL)
    try:
        eng.submit(P2, 2).result(timeout=60)        # both programs compiled
        eng.set_fault_hook(hook)
        req = eng.submit(P1, 6 if how == "drain" else 40,
                         deadline=2.0 if how == "deadline" else None)
        assert at_third.wait(30)
        assert len(req.tokens) == 2     # the chunk's and step 1's
        if how == "close":
            closer = threading.Thread(target=eng.close)
            closer.start()
            assert _wait(lambda: eng.closed)
        elif how == "cancel":
            req.cancel()
        elif how == "deadline":
            time.sleep(max(0.0, req.deadline - time.monotonic()) + 0.01)
        go_on.set()
        if how == "close":
            closer.join(30)
            assert not closer.is_alive()
        if how == "drain":
            assert eng.drain(timeout=30)
            assert req.result(timeout=30) == onp.asarray(lm_generate(
                net, P1[None, :], 6))[0, len(P1):].tolist()
        else:
            with pytest.raises(RequestTimedOut if how == "deadline"
                               else RequestCancelled):
                req.result(timeout=30)
            # steps 2 and 3 were handed over before the request ended.
            # close() reads both before it aborts; a reap finds step 2
            # read and step 3 in flight: its token goes nowhere
            assert len(req.tokens) == (4 if how == "close" else 3)
        n = len(req.tokens)
        assert eng.profiler.invariant_violations == 0
    finally:
        go_on.set()
        eng.close()
    assert req.finished and len(req.tokens) == len(req.t_tokens) == n
    assert req.t_tokens[-1] <= req.t_done
    st = eng.stats()
    assert st["blocks_free"] == st["blocks_total"] and st["active"] == 0


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
        row[0] = 1                          # positions 0..7: prompt + steps
        toks = onp.zeros((progs.prefill_chunk_len,), onp.int32)
        toks[:P] = P1
        keys = onp.zeros((2, 2), onp.uint32)            # seed 0
        progs.gather_params()
        first = int(progs.prefill_chunk(row, toks, 0, P, keys[0], 0, P))
        tables = onp.full((2, nbps), SCRATCH_BLOCK, onp.int32)
        tables[0] = row
        live = onp.array([True, False])
        nxt = progs.step(tables, onp.array([first, 0], onp.int32),
                         onp.array([P, 0], onp.int32), live, keys, live, 1)
        # the step after takes its token from the one before, where it
        # lies: the host's entry (a wrong one here) is not looked at
        last = progs.step(tables, onp.array([V - 1, 0], onp.int32),
                          onp.array([P + 1, 0], onp.int32), live, keys,
                          onp.array([False, False]), 1)
        want = clean_engine.submit(P1, 3, seed=0).result(timeout=60)
        assert [first, int(nxt[0]), int(last[0])] == want
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
    # a speculating engine reads each window before it hands the next over
    ahead = 0 if flavour == "speculative" else 1
    assert eng.varz_config()["steps_in_flight"] == ahead
    assert eng.stats()["steps_ahead"] == ahead      # of the 2 steps it ran
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


def _hybrid_net():
    from incubator_mxnet_tpu.models.hybrid_ssm import HybridSSMDecoder

    net = HybridSSMDecoder(
        vocab_size=V, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=1,
        attn_layer_period=4, attn_layer_offset=2, mamba_d_state=4,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=6,
        max_position_embeddings=MAXLEN)
    net.initialize()
    return net


def _routed_net():
    from incubator_mxnet_tpu.models.routed_window import RoutedWindowDecoder

    net = RoutedWindowDecoder(
        vocab_size=V, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=8, num_key_value_heads=2,
        swa_num_key_value_heads=4, head_dim=12, v_head_dim=8,
        hybrid_layer_pattern=[0, 1, 0], moe_layer_freq=[0, 1, 1],
        sliding_window=10, moe_intermediate_size=16, n_routed_experts=8,
        n_routed_experts_published=32, num_experts_per_tok=4,
        max_position_embeddings=MAXLEN)
    net.initialize()
    return net


@pytest.mark.parametrize("decoder,kw,want", [
    ("transformer", {"attn_impl": "pallas"}, "window"),
    ("hybrid_ssm", {"attn_impl": "pallas"}, "window"),
    ("routed_window", {"attn_impl": "pallas"}, "window"),
    ("transformer", {"attn_impl": "pallas", "kv_dtype": "int8"}, "lanes"),
    ("transformer", {"attn_impl": "dense"}, "dense"),
    ("hybrid_ssm", {}, "dense"),                 # the CPU's default
    ("routed_window", {"attn_impl": "dense"}, "dense"),
], ids=["transformer_pallas", "hybrid_pallas", "routed_pallas",
        "int8_pages", "transformer_dense", "hybrid_default", "routed_dense"])
def test_engine_says_how_a_chunk_attends(net, decoder, kw, want):
    """`varz_config()["chunk_attn"]`, static for an engine and a function
    of shapes, K/V dtype and implementation alone: "window" where a
    prefill chunk's queries walk the sequence's pages once together
    (`paged_attention_window`: every float-paged kernel engine, whatever
    the decoder's head widths), "lanes" where each is a lane of the
    single-query kernel (int8 pages), "dense" where no kernel runs; the
    `paged_attn_chunk{form=}` gauge says the same beside
    `paged_attn_kernel{path=}`."""
    from incubator_mxnet_tpu import telemetry

    served = {"transformer": lambda: net, "hybrid_ssm": _hybrid_net,
              "routed_window": _routed_net}[decoder]()
    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        with ServingEngine(served, max_batch=2, block_size=8,
                           poll_interval=_POLL, **kw) as eng:
            cfg = eng.varz_config()
            assert cfg["chunk_attn"] == want == eng._programs.chunk_attn
            assert (cfg["paged_pages_per_step"] > 0) == (want != "dense")
            for form in ("window", "lanes", "dense"):
                gauge = telemetry.gauge("paged_attn_chunk",
                                        labels={"form": form})
                assert gauge.value == (1.0 if form == want else 0.0)
    finally:
        if not was_on:
            telemetry.disable()
