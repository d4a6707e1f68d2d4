"""Autoregressive decode-engine suite (``pytest -m decode`` / ``make decode``).

Covers the docs/SERVING.md "Autoregressive decode" contracts:

1. paged KV cache — ``pages_for``/bucket math, all-or-nothing allocation,
   LIFO reuse, double-free/unknown-free leak guards, the reserved scratch
   page, and the ``decode.kv_pages_used`` gauge;
2. continuous batching — token-level join/leave between steps, priority
   lanes, the batcher shed discipline (aggregate == sum(by_reason)),
   mid-generation deadline/cancel/page-exhaustion retirement, and the
   page-leak-free guarantee (every exit funnels through ``_retire``);
3. the two-program bound — ``warmup()`` compiles exactly one prefill per
   prompt bucket plus ONE decode-step program, ANY traffic mix compiles
   nothing further, and ``TraceLinter.check_decode_engine`` returning an
   empty list IS the proof;
4. numerics — the paged engine's greedy stream is bitwise-identical to a
   dense full-forward reference, prefill matches the training-path
   forward, and the decode-shape attention kernels (XLA gather vs the
   Pallas kernel in interpret mode) agree with a naive reference;
5. the streaming wire — TOKEN/END/ERROR chunk framing, typed shed errors
   mid-stream, pre-commit retry vs post-commit "stream broken", chaos
   drop/dup on the stream opcode, client hang-up reclaiming pages, and
   the fleet front relaying replica streams with failover-before-first-
   token plus one merged client→front→replica trace timeline;
6. process-level chaos — a replica SIGKILLed mid-stream (``serve:
   mid_stream`` kill point) surfaces as the post-commit stream error;
   a progcache-warmed replica performs ZERO fresh XLA compiles.
"""
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, obs
from mxnet_tpu.analysis.findings import Severity
from mxnet_tpu.analysis.trace import TraceLinter
from mxnet_tpu.chaos import rpc as chaos_rpc
from mxnet_tpu.models.transformer import (decode_config, decode_params,
                                          lm_prefill, sample_token,
                                          transformer_lm)
from mxnet_tpu.obs import context as obs_context
from mxnet_tpu.serve import (DeadlineExceeded, DecodeEngine, DecodeScheduler,
                             Draining, PageLeakError, PagePool,
                             PagesExhausted, RequestRejected, ServeClient,
                             ServeError, ServeServer, default_decode_buckets)
from mxnet_tpu.serve.fleet import FleetServer, ReplicaPool, Router
from mxnet_tpu.serve.kvcache import SCRATCH_PAGE, pages_for

pytestmark = pytest.mark.decode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    chaos_rpc.reset()
    yield
    chaos_rpc.reset()
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# fixtures: one tiny LM + one warmed engine + one wire stack per module
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    # the same weights in every run (conftest's per-test seed is drawn from
    # the host's entropy, and this fixture is built inside whichever test
    # asks for it first): what a test below reads of the served tokens must
    # not change from run to run
    mx.random.seed(20260)
    model = transformer_lm(vocab_size=97, units=32, hidden_size=64,
                           num_layers=2, num_heads=4, max_length=64,
                           dropout=0.0)
    model.initialize()
    model(nd.zeros((1, 8)))  # deferred-init shape inference
    return model


@pytest.fixture(scope="module")
def engine(lm):
    eng = DecodeEngine(lm, slots=4, page_size=8, num_pages=16,
                       prompt_buckets=[8, 16])
    eng._warmup_fresh = eng.warmup()
    return eng


@pytest.fixture(scope="module")
def stack(engine):
    """Started decode server + client sharing the module engine."""
    sched = DecodeScheduler(engine, max_new_tokens=6)
    srv = ServeServer(engine=None, decode=sched, port=0)
    srv.start()
    cli = ServeClient("127.0.0.1", srv.port, retries=2)
    yield engine, sched, srv, cli
    cli.close()
    srv.stop()
    engine.pool.assert_baseline()


def _wait(pred, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# 1. paged KV cache
# ---------------------------------------------------------------------------

def test_pages_for_and_default_buckets():
    assert pages_for(0, 16) == 0
    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2
    # powers of two from page_size, then the exact cap
    assert default_decode_buckets(100, 16) == [16, 32, 64, 112]
    assert default_decode_buckets(64, 16) == [16, 32, 64]
    assert default_decode_buckets(8, 8) == [8]
    for b in default_decode_buckets(100, 16):
        assert b % 16 == 0


def test_page_pool_alloc_free_lifo_reuse():
    pool = PagePool(8, 4)
    assert pool.capacity() == 7  # page 0 reserved for scratch
    pool.alloc("a", 3)
    ta = list(pool.table("a"))
    assert len(ta) == 3 and SCRATCH_PAGE not in ta
    pool.alloc("b", 2)
    assert pool.used() == 5 and pool.available() == 2
    pool.free("a")
    pool.alloc("c", 3)
    # LIFO free list: c reuses a's pages (hot KV pages stay hot)
    assert set(pool.table("c")) == set(ta)
    pool.free("b")
    pool.free("c")
    pool.assert_baseline()
    st = pool.stats()
    assert st["peak_used"] == 5 and st["used"] == 0


def test_page_pool_all_or_nothing_and_leak_guards():
    pool = PagePool(4, 2)  # capacity 3
    pool.alloc("a", 2)
    with pytest.raises(PagesExhausted):
        pool.alloc("b", 2)  # only 1 free: must take NOTHING
    assert pool.used() == 2 and pool.sequences() == 1  # "b" took nothing
    with pytest.raises(PageLeakError):
        pool.free("never-allocated")
    pool.free("a")
    with pytest.raises(PageLeakError):
        pool.free("a")  # double free
    with pytest.raises(PageLeakError):
        pool.table("a")
    pool.alloc("c", 1)
    with pytest.raises(PageLeakError):
        pool.assert_baseline()
    pool.free("c")
    pool.assert_baseline()
    assert PagesExhausted.__mro__[1] is RequestRejected  # shed, not bug


def test_page_pool_gauge_tracks_usage():
    obs.enable()
    pool = PagePool(8, 4)
    pool.alloc("a", 3)
    assert obs.metrics.snapshot()["gauges"]["decode.kv_pages_used"] == 3
    pool.free("a")
    assert obs.metrics.snapshot()["gauges"]["decode.kv_pages_used"] == 0


# ---------------------------------------------------------------------------
# 2. continuous batching (duck-typed engine: deterministic + optionally slow)
# ---------------------------------------------------------------------------

class _FakeDecodeEngine:
    """Scheduler-facing engine stub: token streams are a pure function of
    the prompt (prefill = sum(prompt) % 1000, then +1 mod 997 per step),
    so join/leave mixing is decidable without racing real XLA. Like the
    real engine it keeps every slot's last token itself, and a launched
    call is done ``delay`` after the one before it (one queue, in order):
    ``read`` waits for that."""

    def __init__(self, slots=2, page_size=4, num_pages=64, max_length=64,
                 delay=0.0):
        self.slots = slots
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_length = max_length
        self.max_pages = min(pages_for(max_length, page_size),
                             num_pages - 1)
        self.buckets = default_decode_buckets(
            min(max_length, (num_pages - 1) * page_size), page_size)
        self.pool = PagePool(num_pages, page_size)
        self.delay = delay
        self.compile_log = []
        self.prefill_order = []
        self.last = np.zeros((slots,), np.int32)
        self.steps = []         # every launched step's packed argument
        self.calls = []         # "prefill"/"step" launched, "read", in order
        self._done_at = 0.0

    def bucket_for(self, n):
        for b in self.buckets:
            if b >= n:
                return b
        raise RequestRejected(f"prompt length {n} exceeds max bucket")

    def _queued(self, out):
        self._done_at = max(self._done_at, time.monotonic()) + self.delay
        return self._done_at, out

    def launch_prefill(self, tokens, page_ids, *, temperature=0.0, seed=0,
                       slot=0):
        tok = int(np.sum(tokens) % 1000)
        self.prefill_order.append(tok)
        self.calls.append("prefill")
        self.last[slot] = tok
        return self._queued(tok)

    def blank_step(self):
        return np.zeros((self.slots + 1, 3 + self.max_pages), np.int32)

    def launch_step(self, packed):
        self.steps.append(packed)
        self.calls.append("step")
        self.last = ((self.last.astype(np.int64) + 1) % 997).astype(np.int32)
        return self._queued(self.last.copy())

    def read(self, launched):
        done_at, out = launched
        self.calls.append("read")
        time.sleep(max(0.0, done_at - time.monotonic()))
        return out, {}

    def warmup(self):
        return 0

    def stats(self):
        return {"fake": True}


def _fake_seq(prompt, n):
    out = [int(np.sum(prompt) % 1000)]
    while len(out) < n:
        out.append((out[-1] + 1) % 997)
    return out


def test_continuous_batching_join_leave():
    eng = _FakeDecodeEngine(slots=2, delay=0.005)
    sched = DecodeScheduler(eng, max_new_tokens=8)
    try:
        prompts = [[1], [2, 3], [4, 5, 6], [7]]
        wants = [5, 9, 3, 7]
        got = [None] * 4

        def run(i):
            got[i] = list(sched.generate(prompts[i],
                                         max_new_tokens=wants[i]))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(4)]
        for i, t in enumerate(threads):
            t.start()
            time.sleep(0.01 * i)  # stagger: join/leave mid-batch
        for t in threads:
            t.join(10)
        for i in range(4):
            assert got[i] == _fake_seq(prompts[i], wants[i]), i
        st = sched.stats()
        assert st["completed"] == 4
        assert st["tokens_out"] == sum(wants)
        assert st["shed"] == sum(st["shed_by_reason"].values()) == 0
        assert st["occupancy"] > 0
        eng.pool.assert_baseline()
    finally:
        sched.close()
    assert sched.stopped_clean


def test_priority_lane_admitted_first():
    eng = _FakeDecodeEngine(slots=1, delay=0.03)
    sched = DecodeScheduler(eng, lanes=2, max_new_tokens=12)
    try:
        sched.submit([1], max_new_tokens=12)       # occupies the slot
        _wait(lambda: eng.prefill_order == [1], msg="first admit")
        sched.submit([2], priority=1, max_new_tokens=2)
        sched.submit([3], priority=0, max_new_tokens=2)
        _wait(lambda: sched.stats()["completed"] == 3, timeout=10,
              msg="all complete")
        # lane 0 drains first: [3] jumps the earlier-submitted [2]
        assert eng.prefill_order == [1, 3, 2]
        eng.pool.assert_baseline()
    finally:
        sched.close()


def test_shed_discipline_aggregate_equals_by_reason():
    eng = _FakeDecodeEngine(slots=1, delay=0.05)
    sched = DecodeScheduler(eng, max_queue=2, max_new_tokens=30)
    try:
        h0 = sched.submit([1], max_new_tokens=30)
        _wait(lambda: sched.stats()["active"] == 1
              and sched.stats()["queued"] == 0, msg="h0 admitted")
        # dead on arrival (queue has room, so it reaches the deadline check)
        with pytest.raises(DeadlineExceeded):
            sched.submit([9], deadline_ms=0.0)
        h1 = sched.submit([2], max_new_tokens=5)
        h2 = sched.submit([3], max_new_tokens=5)
        with pytest.raises(RequestRejected):
            sched.submit([4])  # queue over watermark
        for h in (h0, h1, h2):
            h.cancel()
        assert sched.drain(timeout=10)
        with pytest.raises(Draining):
            sched.submit([5])
        st = sched.stats()
        assert st["shed"] == sum(st["shed_by_reason"].values()) == 3
        assert st["shed_by_reason"]["deadline"] == 1
        assert st["shed_by_reason"]["queue_full"] == 1
        assert st["shed_by_reason"]["draining"] == 1
        eng.pool.assert_baseline()
    finally:
        sched.close()


def test_deadline_expires_mid_generation():
    eng = _FakeDecodeEngine(slots=1, delay=0.03)
    sched = DecodeScheduler(eng)
    try:
        got = []
        with pytest.raises(DeadlineExceeded):
            for tok in sched.generate([1, 2, 3], max_new_tokens=100,
                                      deadline_ms=150):
                got.append(tok)
        assert got  # tokens WERE flowing before the deadline landed
        assert sched.stats()["shed_by_reason"]["deadline"] == 1
        eng.pool.assert_baseline()
    finally:
        sched.close()


def test_cancel_reclaims_pages_and_batch_keeps_running():
    eng = _FakeDecodeEngine(slots=2, delay=0.02)
    sched = DecodeScheduler(eng)
    try:
        gen = sched.generate([5, 6], max_new_tokens=50)
        assert next(gen) == _fake_seq([5, 6], 1)[0]
        next(gen)
        gen.close()  # hang-up is the cancel signal
        _wait(lambda: eng.pool.used() == 0, msg="page reclaim")
        assert sched.stats()["cancelled"] == 1
        # the scheduler is still healthy for the next stream
        assert list(sched.generate([7], max_new_tokens=3)) == \
            _fake_seq([7], 3)
    finally:
        sched.close()


def test_page_exhaustion_queues_then_sheds_running_stream():
    # capacity 2 pages of 4 positions, max_length 8
    eng = _FakeDecodeEngine(slots=2, page_size=4, num_pages=3,
                            max_length=8, delay=0.02)
    sched = DecodeScheduler(eng)
    try:
        # A's bucket-8 prompt takes BOTH pages at admission; B must wait
        # queued (admission exhaustion is not a shed) until A retires
        got_a, got_b = [], []

        def run_a():
            got_a.extend(sched.generate([1, 2, 3, 4, 5],
                                        max_new_tokens=3))

        def run_b():
            got_b.extend(sched.generate([9], max_new_tokens=2))

        ta = threading.Thread(target=run_a)
        tb = threading.Thread(target=run_b)
        ta.start()
        _wait(lambda: eng.pool.used() == 2, msg="A admitted")
        tb.start()
        ta.join(10)
        tb.join(10)
        assert got_a == _fake_seq([1, 2, 3, 4, 5], 3)
        assert got_b == _fake_seq([9], 2)
        assert sched.stats()["shed_by_reason"]["pages"] == 0
        eng.pool.assert_baseline()
    finally:
        sched.close()

    # mid-generation growth past the pool sheds the RUNNING stream with
    # reason "pages" and frees its pages so the batch keeps stepping
    eng2 = _FakeDecodeEngine(slots=1, page_size=4, num_pages=3,
                             max_length=64)
    sched2 = DecodeScheduler(eng2)
    try:
        got = []
        with pytest.raises(PagesExhausted):
            for tok in sched2.generate([1, 2, 3], max_new_tokens=40):
                got.append(tok)
        assert got  # it was generating before the pool ran dry
        assert sched2.stats()["shed_by_reason"]["pages"] == 1
        eng2.pool.assert_baseline()
    finally:
        sched2.close()


# -- the next step is launched before the last one's tokens are read --------

def test_steps_are_launched_ahead_and_counted():
    """In a steady batch every step but the first is launched while the
    one before it is still in flight, and its position is counted from the
    tokens launched, not from those that have come back."""
    eng = _FakeDecodeEngine(slots=2, delay=0.005)
    sched = DecodeScheduler(eng)
    try:
        assert list(sched.generate([1, 2], max_new_tokens=9)) == \
            _fake_seq([1, 2], 9)
        assert sched.drain(timeout=5)
        st = sched.stats()
        assert st["steps"] == st["steps_launched"] == 8
        assert st["launched_ahead"] == 7
        assert st["launched_ahead_share"] == 7 / 8
        assert st["dropped_speculative"] == 0
        # slot 0's row of every step: position and length follow the count
        assert [(int(p[0, 0]), int(p[0, 1])) for p in eng.steps] == [
            (2 + i, 3 + i) for i in range(8)]
        eng.pool.assert_baseline()
    finally:
        sched.close()


def test_a_finished_streams_slot_is_refilled_behind_its_last_step():
    """A stream's last step by ``max_new_tokens`` is known as it is
    launched, so its slot and pages go back then: the queued request's
    prefill is launched behind that step, before the step's token has been
    read, and no step runs with the slot empty."""
    eng = _FakeDecodeEngine(slots=1, delay=0.02)
    sched = DecodeScheduler(eng)
    try:
        a = sched.submit([1], max_new_tokens=3)
        b = sched.submit([2], max_new_tokens=2)
        got = {a: [], b: []}
        for h in (a, b):
            while not got[h] or got[h][-1][0] == "token":
                got[h].append(h.get(timeout=5))
        assert [ev[1] for ev in got[a][:-1]] == _fake_seq([1], 3)
        assert [ev[1] for ev in got[b][:-1]] == _fake_seq([2], 2)
        assert sched.drain(timeout=5)
        # A: prefill, two steps; B's prefill and step go out before A's
        # last step is read
        assert eng.calls == ["prefill", "step", "read", "step", "read",
                             "prefill", "step", "read", "read", "read"]
        assert sched.stats()["dropped_speculative"] == 0
        eng.pool.assert_baseline()
    finally:
        sched.close()


def test_eos_ends_the_stream_and_drops_the_step_in_flight():
    eng = _FakeDecodeEngine(slots=2, delay=0.005)
    sched = DecodeScheduler(eng, eos_id=8)
    obs.enable()
    try:
        # 5, 6, 7, 8 = EOS: nothing after it, though a step was in flight
        assert list(sched.generate([5], max_new_tokens=30)) == [5, 6, 7, 8]
        assert sched.drain(timeout=5)
        st = sched.stats()
        assert eng.pool.used() == 0
        assert st["dropped_speculative"] == 1 and st["tokens_out"] == 4
        assert st["steps_launched"] == st["steps"] == 4
        counters = obs.metrics.snapshot()["counters"]
        assert counters["decode.dropped_speculative"] == 1
        assert counters["decode.launched_ahead"] == st["launched_ahead"] == 3
    finally:
        sched.close()


def test_eos_on_the_real_engine_frees_pages_the_next_stream_reuses(engine):
    prompt = np.array([3, 1, 4, 1, 5], np.int32)
    other = np.array([2, 7, 1, 8, 2, 8, 1, 8, 2], np.int32)
    plain = DecodeScheduler(engine)
    try:
        want = list(plain.generate(prompt, max_new_tokens=6))
        want_other = list(plain.generate(other, max_new_tokens=6))
    finally:
        plain.close()
    # the first token that is new to the stream ends it; a model that only
    # repeats itself (3 random initialisations of 200 serve 5, 5, 5, ...:
    # the StopIteration that failed this test under the driver) ends at its
    # first token, behind which a step is in flight just the same
    cut = next((i for i in range(1, 6) if want[i] not in want[:i]), 0)
    sched = DecodeScheduler(engine, eos_id=want[cut])
    try:
        assert list(sched.generate(prompt, max_new_tokens=6)) == \
            want[:cut + 1]
        # the dropped step wrote a row into a page that is free again:
        # whoever takes the page next is not disturbed by it
        stop = next((i for i, t in enumerate(want_other)
                     if t == want[cut]), 5)
        assert list(sched.generate(other, max_new_tokens=6)) == \
            want_other[:stop + 1]
        assert sched.drain(timeout=10)
        assert sched.stats()["dropped_speculative"] >= 1
        assert engine.pool.used() == 0
    finally:
        sched.close()
    engine.pool.assert_baseline()


@pytest.mark.parametrize("reason", ["cancelled", "deadline", "backpressure"])
def test_late_learned_exits_retire_with_a_step_in_flight(reason):
    """What the host learns only when a token is handed over costs the
    slot one speculative step: the stream retires through ``_retire``, the
    token of the step already launched is dropped, no page leaks, and the
    batch keeps running for the stream beside it."""
    eng = _FakeDecodeEngine(slots=2, delay=0.01)
    sched = DecodeScheduler(eng)
    try:
        beside = sched.submit([7], max_new_tokens=40)
        h = sched.submit([1, 2, 3], max_new_tokens=40,
                         deadline_ms=60 if reason == "deadline" else None)
        if reason == "backpressure":
            emit = h._emit
            h._emit = lambda ev, *arrived: (
                (ev[0] != "token" or ev[2] <= 3) and emit(ev, *arrived))
        events = []
        while not events or events[-1][0] == "token":
            events.append(h.get(timeout=5))
            if reason == "cancelled" and len(events) == 3:
                h.cancel()
        tokens = [ev[1] for ev in events if ev[0] == "token"]
        assert tokens and tokens == _fake_seq([1, 2, 3], len(tokens))
        last = events[-1]
        if reason == "cancelled":
            assert last[:2] == ("end", "cancelled") and last[2] == len(tokens)
        elif reason == "deadline":
            assert isinstance(last[1], DeadlineExceeded)
        else:
            assert isinstance(last[1], RequestRejected) and len(tokens) == 3
        _wait(lambda: sched.stats()["dropped_speculative"] == 1,
              msg="the step in flight read and dropped")
        got = []
        while not got or got[-1][0] == "token":
            got.append(beside.get(timeout=5))
        assert [ev[1] for ev in got[:-1]] == _fake_seq([7], 40)
        assert sched.drain(timeout=5)
        st = sched.stats()
        assert st["dropped_speculative"] == 1
        assert st["shed"] == sum(st["shed_by_reason"].values())
        assert st["cancelled"] == (reason == "cancelled")
        eng.pool.assert_baseline()
    finally:
        sched.close()


def test_page_exhaustion_with_a_step_in_flight_sheds_the_stream_that_grows():
    # 3 pages of 4 positions: A and B hold one each, both outgrow it at
    # position 4, and there is one page left
    eng = _FakeDecodeEngine(slots=2, page_size=4, num_pages=4,
                            max_length=64, delay=0.01)
    sched = DecodeScheduler(eng)
    try:
        a = sched.submit([1, 2, 3], max_new_tokens=6)
        b = sched.submit([4, 5, 6], max_new_tokens=6)
        got = {a: [], b: []}
        for h in (a, b):
            while not got[h] or got[h][-1][0] == "token":
                got[h].append(h.get(timeout=5))
        # A, first in the batch, grew; B was shed while its first step was
        # in flight, and that step's token is dropped, not delivered late
        assert [ev[1] for ev in got[a][:-1]] == _fake_seq([1, 2, 3], 6)
        assert got[a][-1][:2] == ("end", "length")
        assert [ev[1] for ev in got[b][:-1]] == _fake_seq([4, 5, 6], 1)
        assert isinstance(got[b][-1][1], PagesExhausted)
        assert sched.drain(timeout=5)
        st = sched.stats()
        assert st["shed_by_reason"]["pages"] == st["shed"] == 1
        assert st["dropped_speculative"] == 1
        eng.pool.assert_baseline()
    finally:
        sched.close()


# -- a prompt goes in pieces, one a turn, between the steps ------------------

class _FakePieceEngine(_FakeDecodeEngine):
    """The stub as an engine whose model can continue a prompt: a prompt is
    padded to a multiple of ``piece`` and ``launch_prefill`` queues ONE piece
    of it; the last piece's token is the prompt's. ``calls`` says which."""

    def __init__(self, piece=4, **kw):
        super().__init__(**kw)
        self.prefill_piece = piece
        self.max_prompt = self.buckets[-1]
        self.buckets = [piece]
        self.pieces = []        # (slot, start) of every piece launched

    def bucket_for(self, n):
        if n > self.max_prompt:
            raise RequestRejected(f"prompt length {n} exceeds max bucket")
        return -(-n // self.prefill_piece) * self.prefill_piece

    def launch_prefill(self, tokens, page_ids, *, temperature=0.0, seed=0,
                       slot=0, start=0):
        assert start % self.prefill_piece == 0 and start < len(tokens)
        assert len(page_ids) == self.bucket_for(len(tokens)) // self.page_size
        self.pieces.append((slot, start))
        if start + self.prefill_piece < len(tokens):
            self.calls.append("piece")
            return self._queued(-1)
        return super().launch_prefill(tokens, page_ids, slot=slot)


def _events(handle, timeout=5):
    events = []
    while not events or events[-1][0] == "token":
        events.append(handle.get(timeout=timeout))
    return events


def test_a_prompt_goes_one_piece_a_turn_between_the_steps():
    """A 14-token prompt in pieces of 4 beside a decoding stream: four
    pieces, never two of them between two steps; the prefilling slot rides
    those steps idle and joins the batch with its last piece; the decoding
    stream's tokens are what it gets alone."""
    eng = _FakePieceEngine(piece=4, slots=2, delay=0.003)
    sched = DecodeScheduler(eng)
    obs.enable()
    try:
        a = sched.submit([5], max_new_tokens=40)
        _wait(lambda: sched.stats()["steps"] >= 2, msg="A decoding")
        prompt = list(range(1, 15))
        b = sched.submit(prompt, max_new_tokens=6)
        got_b, got_a = _events(b), _events(a)
        assert [ev[1] for ev in got_a[:-1]] == _fake_seq([5], 40)
        assert [ev[1] for ev in got_b[:-1]] == _fake_seq(prompt, 6)
        assert sched.drain(timeout=5)
        assert eng.pieces == [(0, 0)] + [(1, s) for s in (0, 4, 8, 12)]
        launched = [c for c in eng.calls if c != "read"]
        first = launched.index("piece")
        between = "".join(c[0] for c in launched[first:])
        # p(iece) s(tep) p s p s p(refill, the last piece) s ...
        assert between.startswith("pspspsps") and "pp" not in between
        # B's slot idle in the steps between its pieces, live after the last
        lengths = [int(p[1, 1]) for p in eng.steps]
        joined = lengths.index(15)
        assert lengths[:joined] == [0] * joined and joined >= 3 + 2
        assert lengths[joined:joined + 5] == [15, 16, 17, 18, 19]
        st = sched.stats()
        assert st["prefill_piece"] == 4 and st["admitted"] == 2
        assert st["prefill_pieces"] == 5
        counters = obs.metrics.snapshot()["counters"]
        assert counters["decode.prefill_pieces"] == 5
        assert counters["decode.admitted"] == 2
        spans = [s for s in obs.trace.drain() if s["name"] == "decode.prefill"]
        assert [(s["args"]["start"], s["args"]["pieces"], s["args"]["bucket"],
                 s["args"]["prompt_len"]) for s in spans] == [
            (0, 1, 4, 1)] + [(s, 4, 4, 14) for s in (0, 4, 8, 12)]
        eng.pool.assert_baseline()
    finally:
        sched.close()


def test_pieces_go_one_a_turn_with_no_stream_decoding_and_in_admission_order():
    """Two prompts admitted in one turn: the older one's pieces first, then
    the other's, every turn one piece — with nothing decoding the turn is the
    piece alone."""
    eng = _FakePieceEngine(piece=4, slots=2, delay=0.002)
    sched = DecodeScheduler(eng)
    try:
        a = sched.submit(list(range(1, 10)), max_new_tokens=6)
        b = sched.submit(list(range(2, 8)), max_new_tokens=2)
        got_a, got_b = _events(a), _events(b)
        assert [ev[1] for ev in got_a[:-1]] == _fake_seq(range(1, 10), 6)
        assert [ev[1] for ev in got_b[:-1]] == _fake_seq(range(2, 8), 2)
        assert sched.drain(timeout=5)
        slot_a = eng.pieces[0][0]
        assert eng.pieces == [(slot_a, 0), (slot_a, 4), (slot_a, 8),
                              (1 - slot_a, 0), (1 - slot_a, 4)]
        # nothing decoding: each piece is read before the next is launched;
        # A's last piece makes it a decoding slot, and B's pieces then go
        # one in front of each of A's steps
        assert eng.calls[:6] == ["piece", "read", "piece", "read",
                                 "prefill", "step"]
        launched = "".join(c[0] for c in eng.calls if c != "read")
        assert launched.startswith("pppspsps"), launched
        assert sched.stats()["prefill_pieces"] == 5
        eng.pool.assert_baseline()
    finally:
        sched.close()


@pytest.mark.parametrize("reason", ["cancelled", "deadline", "backpressure",
                                    "close"])
def test_exits_in_mid_prefill_return_slot_and_pages(reason):
    """A generation that leaves while its prompt is still going in — hung
    up, past its deadline, its first token refused, the scheduler closed —
    goes through ``_release`` like every other: slot and pages back, the
    pieces left are never launched, the stream beside it is not disturbed."""
    eng = _FakePieceEngine(piece=4, slots=2, delay=0.02, num_pages=64,
                           max_length=128)
    sched = DecodeScheduler(eng)
    try:
        beside = sched.submit([7], max_new_tokens=30)
        _wait(lambda: sched.stats()["steps"] >= 1, msg="a stream decoding")
        prompt = list(range(1, 101))        # 25 pieces: a second of them
        h = sched.submit(prompt, max_new_tokens=5,
                         deadline_ms=150 if reason == "deadline" else None)
        if reason == "backpressure":
            emit = h._emit
            h._emit = lambda ev, *arrived: (ev[0] != "token"
                                            and emit(ev, *arrived))
        _wait(lambda: len(eng.pieces) >= 3, msg="the prompt going in")
        assert eng.pool.used() >= 25
        if reason == "cancelled":
            h.cancel()
        if reason == "close":
            sched.close()
            last = h.get(timeout=5)
            assert last[0] == "error" and isinstance(last[1], ServeError)
            assert len(eng.pieces) < 26
            eng.pool.assert_baseline()
            return
        last = _events(h)[-1]
        if reason == "cancelled":
            assert last == ("end", "cancelled", 0)
        elif reason == "deadline":
            assert isinstance(last[1], DeadlineExceeded)
        else:
            assert isinstance(last[1], RequestRejected)
        if reason != "backpressure":     # the rest of the prompt never went
            assert len(eng.pieces) < 26
        # the slot serves the next request, beside the stream still running
        assert list(sched.generate([9, 9], max_new_tokens=3)) == \
            _fake_seq([9, 9], 3)
        assert [ev[1] for ev in _events(beside)[:-1]] == _fake_seq([7], 30)
        assert sched.drain(timeout=5)
        st = sched.stats()
        assert st["shed"] == sum(st["shed_by_reason"].values())
        assert st["shed_by_reason"]["deadline"] == (reason == "deadline")
        assert st["cancelled"] == (reason == "cancelled")
        eng.pool.assert_baseline()
    finally:
        sched.close()


def test_a_model_prefilled_whole_launches_the_calls_it_launched_before():
    """An engine that offers no ``prefill_piece`` is handed every prompt in
    one ``launch_prefill`` with the arguments it always had (the stub's takes
    no ``start``), in the turn it is admitted."""
    eng = _FakeDecodeEngine(slots=2, delay=0.003)
    sched = DecodeScheduler(eng)
    try:
        a = sched.submit([5], max_new_tokens=12)
        _wait(lambda: sched.stats()["steps"] >= 2, msg="A decoding")
        b = sched.submit(list(range(1, 15)), max_new_tokens=4)
        assert [ev[1] for ev in _events(b)[:-1]] == _fake_seq(range(1, 15), 4)
        assert [ev[1] for ev in _events(a)[:-1]] == _fake_seq([5], 12)
        assert sched.drain(timeout=5)
        assert eng.calls.count("prefill") == 2 and "piece" not in eng.calls
        st = sched.stats()
        assert st["prefill_piece"] is None
        assert st["prefill_pieces"] == st["admitted"] == 2
        eng.pool.assert_baseline()
    finally:
        sched.close()


class _Recorded:
    """Every call the scheduler launches on ``engine``, in launch order."""

    def __init__(self, engine, monkeypatch):
        self.calls = []
        launch_prefill, launch_step = engine.launch_prefill, engine.launch_step

        def prefill(tokens, page_ids, **kw):
            self.calls.append(("prefill", np.array(tokens), list(page_ids),
                               kw))
            return launch_prefill(tokens, page_ids, **kw)

        def step(packed):
            self.calls.append(("step", packed.copy()))
            return launch_step(packed)

        monkeypatch.setattr(engine, "launch_prefill", prefill)
        monkeypatch.setattr(engine, "launch_step", step)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_launch_ahead_equals_the_engine_stepped_synchronously(
        lm, monkeypatch, temperature):
    """Seven requests through two slots — joining as others leave — with
    the scheduler launching ahead and the tokens fed back on the device,
    against the same calls made one at a time through ``DecodeEngine.step``
    with the tokens carried by the host: the same tokens, greedy and
    sampled (same seeds)."""
    eng = DecodeEngine(lm, slots=2, page_size=8, num_pages=16,
                       prompt_buckets=[8, 16])
    eng.warmup()
    recorded = _Recorded(eng, monkeypatch)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 90, n).astype(np.int32)
               for n in (3, 9, 5, 12, 2, 7, 16)]
    wants = [5, 9, 2, 7, 1, 12, 4]
    sched = DecodeScheduler(eng)
    try:
        handles = [sched.submit(p, max_new_tokens=n, temperature=temperature,
                                seed=100 + i)
                   for i, (p, n) in enumerate(zip(prompts, wants))]
        got = []
        for h in handles:
            events = []
            while not events or events[-1][0] == "token":
                events.append(h.get(timeout=30))
            assert events[-1][:2] == ("end", "length")
            got.append([ev[1] for ev in events[:-1]])
        assert sched.drain(timeout=10)
        st = sched.stats()
    finally:
        sched.close()
    monkeypatch.undo()
    assert [len(g) for g in got] == wants
    assert st["dropped_speculative"] == 0
    assert st["launched_ahead"] >= st["steps_launched"] - 3
    eng.pool.assert_baseline()

    # the same calls, each waited for, the tokens going through the host
    last = np.zeros((eng.slots,), np.int32)
    owner = [None] * eng.slots
    streams = {}
    for call in recorded.calls:
        if call[0] == "prefill":
            _, tokens, page_ids, kw = call
            tok = eng.prefill(tokens, page_ids, **kw)
            (i,) = [i for i, p in enumerate(prompts)
                    if np.array_equal(p, tokens)]
            owner[kw["slot"]], streams[i] = i, [tok]
            last[kw["slot"]] = tok
            continue
        packed = call[1]
        rows = packed[:eng.slots]
        toks = eng.step(last, rows[:, 0], rows[:, 3:], rows[:, 1],
                        rows[:, 2].view(np.float32),
                        seed=int(packed[eng.slots, 0]))
        for slot in np.flatnonzero(rows[:, 1]):
            streams[owner[slot]].append(int(toks[slot]))
            last[slot] = toks[slot]
    assert [streams[i] for i in range(len(prompts))] == got
    if temperature:
        # and the sampled streams are not the greedy ones
        greedy = DecodeScheduler(eng)
        try:
            assert got[5] != list(greedy.generate(prompts[5],
                                                  max_new_tokens=12))
        finally:
            greedy.close()


def test_program_count_after_warmup_and_after_traffic(lm):
    eng = DecodeEngine(lm, slots=2, page_size=8, num_pages=16,
                       prompt_buckets=[8, 16])
    eng.warmup()
    assert eng.stats()["num_programs"] == len(eng.buckets) + 1 == 3
    sched = DecodeScheduler(eng)
    try:
        for n in (2, 8, 11, 16):
            assert len(list(sched.generate(list(range(1, n + 1)),
                                           max_new_tokens=5))) == 5
    finally:
        sched.close()
    stats = eng.stats()
    assert stats["num_programs"] == len(eng.buckets) + 1
    assert len(eng.compile_log) == 3
    # one host array a call, whatever the traffic: the programs' signatures
    assert sorted(stats["programs"]) == sorted(repr(s) for s in (
        ("prefill", ((4 + 1 + 8,), "int32")),
        ("prefill", ((4 + 2 + 16,), "int32")),
        ("step", ((2 + 1, 3 + eng.max_pages), "int32"))))
    assert TraceLinter().check_decode_engine(eng) == []


# ---------------------------------------------------------------------------
# 3. the two-program bound (real engine)
# ---------------------------------------------------------------------------

def test_two_program_bound_over_mixed_traffic(stack):
    eng, sched, _srv, _cli = stack
    assert eng.buckets == [8, 16]
    # warmup compiled one program per bucket + ONE step program, once
    assert eng._warmup_fresh == len(eng.buckets) + 1
    assert eng.warmup() == 0  # idempotent: nothing left to compile
    n_before = len(eng.compile_log)
    for n in (3, 5, 8, 9, 13, 16):
        prompt = np.arange(1, n + 1, dtype=np.int64) % 90 + 1
        toks = list(sched.generate(prompt, max_new_tokens=4))
        assert len(toks) == 4
    # ANY prompt-length mix retraces nothing
    assert len(eng.compile_log) == n_before
    sigs = {repr(e["sig"]) for e in eng.compile_log}
    assert len(sigs) == len(eng.buckets) + 1
    assert len({repr(e["sig"]) for e in eng.compile_log
                if e["kind"] == "step"}) == 1
    # the linter's empty finding list IS the proof
    assert TraceLinter().check_decode_engine(eng) == []
    eng.pool.assert_baseline()
    with pytest.raises(RequestRejected):
        eng.bucket_for(17)  # over the largest bucket: shed, not compile


def test_check_decode_engine_flags_churn():
    class _Churn:
        buckets = [8]
        compile_log = [
            {"sig": ("prefill", ((1, 8), "int32")), "kind": "prefill"},
            {"sig": ("prefill", ((1, 8), "int32")), "kind": "prefill"},
            {"sig": ("prefill", ((1, 16), "int32")), "kind": "prefill"},
            {"sig": ("step", ((4,), "int32")), "kind": "step"},
            {"sig": ("step", ((8,), "int32")), "kind": "step"},
        ]

    findings = TraceLinter().check_decode_engine(_Churn())
    rules = [f.rule_id for f in findings]
    assert rules.count("decode-retrace-churn") == 3  # dup + buckets + step
    assert all(f.severity == Severity.ERROR for f in findings)
    # clean engines stay clean under the baseline slice
    assert TraceLinter().check_decode_engine(
        _Churn(), baseline=len(_Churn.compile_log)) == []


# ---------------------------------------------------------------------------
# 4. numerics
# ---------------------------------------------------------------------------

def test_prefill_matches_training_forward(lm):
    toks = np.random.randint(1, 97, size=(2, 8))
    ref = lm(nd.array(toks)).asnumpy()
    cfg, params = decode_config(lm), decode_params(lm)
    logits, _k, _v = lm_prefill(cfg, params, toks.astype(np.int32))
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=1e-4,
                               atol=1e-4)


def test_sample_token_greedy_and_temperature():
    import jax
    import jax.numpy as jnp
    logits = jnp.asarray([[0.1, 3.0, -1.0], [5.0, 0.0, 0.0]], jnp.float32)
    key = jax.random.PRNGKey(0)
    out = np.asarray(sample_token(logits, key, 0.0))
    assert out.tolist() == [1, 0] and out.dtype == np.int32
    # per-row temperature: row 0 greedy, row 1 drawn (valid + reproducible)
    t = jnp.asarray([0.0, 1.0], jnp.float32)
    a = np.asarray(sample_token(logits, key, t))
    b = np.asarray(sample_token(logits, key, t))
    assert a[0] == 1 and 0 <= a[1] < 3
    assert a.tolist() == b.tolist()


def _paged_case():
    """A shared pool of three layers and a page table with everything a
    decode batch can hold: a mid-page length, a full table, an inactive
    row on scratch pages only, and a row that repeats a page, shares one
    with another row and pads with the scratch page."""
    rng = np.random.RandomState(3)
    n_pages, layers, page, heads, dim = 9, 3, 4, 2, 8
    q = rng.randn(4, heads, dim).astype(np.float32)
    pool = rng.randn(n_pages, layers, page, heads,
                     2 * dim).astype(np.float32)
    table = np.full((4, 4), SCRATCH_PAGE, np.int32)
    table[0, :2] = [1, 2]
    table[1] = [3, 4, 5, 6]
    table[3, :3] = [7, 7, 3]
    lengths = np.array([5, 16, 0, 10], np.int32)  # row 2 inactive
    return q, pool, table, lengths


def _paged_reference(q, pool, layer, table, lengths):
    """Row by row in numpy: K is ``pool[p, layer, :, :, :D]``, V the other
    half. Inactive rows (length 0) are left NaN: garbage by contract."""
    dim = q.shape[-1]
    out = np.full(q.shape, np.nan, np.float32)
    for i, ln in enumerate(lengths):
        if ln == 0:
            continue
        rows = np.concatenate([pool[p, layer] for p in table[i]], 0)[:ln]
        ks, vs = rows[..., :dim], rows[..., dim:]
        s = np.einsum("hd,lhd->hl", q[i], ks) / math.sqrt(dim)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hl,lhd->hd", p, vs)
    return out


def _paged_impl(name):
    from mxnet_tpu.ops.flash_attention import (_decode_attention_xla,
                                               decode_attention,
                                               flash_decode_attention)
    return {
        "xla": lambda q, *a: _decode_attention_xla(
            q, *a, 1.0 / math.sqrt(q.shape[-1])),
        "dispatch": decode_attention,
        "pallas": lambda *a: flash_decode_attention(*a, interpret=True),
    }[name]


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("impl", ["xla", "dispatch", "pallas"])
def test_decode_attention_parity(impl, layer):
    """Every path reads layer ``layer`` of the one shared pool, K and V
    from their halves of the minor axis, and agrees with numpy."""
    q, pool, table, lengths = _paged_case()
    out = np.asarray(_paged_impl(impl)(q, pool, layer, table, lengths))
    want = _paged_reference(q, pool, layer, table, lengths)
    assert out.shape == q.shape and np.all(np.isfinite(out))
    live = lengths > 0
    np.testing.assert_allclose(out[live], want[live], rtol=2e-5, atol=2e-5)


def _set_page_group(monkeypatch, pool, max_pages, group):
    """Make the kernel read ``group`` pages a grid step over this tiny pool
    by shrinking the one thing G follows besides the shapes, the VMEM
    budget: a page's block is whole (8, 128) float32 tiles."""
    from mxnet_tpu.ops import flash_attention

    page, heads, row = pool.shape[2:]
    block = page * -(-heads // 8) * 8 * -(-row // 128) * 128 * 4
    monkeypatch.setattr(flash_attention, "_DECODE_GROUP_BYTES",
                        block * group)
    assert flash_attention.decode_page_group(pool.shape,
                                             max_pages) == group


@pytest.mark.parametrize("group", [4, 2, 3])
@pytest.mark.parametrize("impl", ["xla", "dispatch", "pallas"])
def test_decode_attention_reads_only_its_layer(impl, group, monkeypatch):
    """Rewriting every OTHER layer's pages (and the dead tail of the pages
    a row holds, which with pages read in groups of 2, 3 or all 4 is also
    the dead tail of a group) changes no bit of layer 1's attention: the
    paths address ``(page, layer)``, not a copy or a neighbour of it."""
    q, pool, table, lengths = _paged_case()
    _set_page_group(monkeypatch, pool, table.shape[1], group)
    lengths[1] = 8            # row 1 lists pages 3-6 and holds 3, 4 only
    fn = _paged_impl(impl)
    before = np.asarray(fn(q, pool, 1, table, lengths))
    other = pool.copy()
    other[:, [0, 2]] = -other[:, [0, 2]] + 3.0
    other[2, 1, 1:] = 7.0     # row 0 holds 5 positions: page 2 from slot 1
    other[[5, 6], 1] = 9.0    # whole dead pages inside row 1's group
    other[SCRATCH_PAGE, 1] = -5.0   # what pads every table
    after = np.asarray(fn(q, other, 1, table, lengths))
    live = lengths > 0
    np.testing.assert_array_equal(before[live], after[live])


def _grouped_case(max_pages, group, page=4):
    """Every length a group boundary can meet — 0, 1, a page less one, a
    page, a group less one, a group, a group and one, the full table —
    over a shared three-layer pool; tables in position order, padded with
    the scratch page, one row reusing a page another row holds."""
    rng = np.random.RandomState(7)
    heads, dim = 2, 8
    full = max_pages * page
    lengths = np.minimum(
        [0, 1, page - 1, page, group * page - 1, group * page,
         group * page + 1, full], full).astype(np.int32)
    n_pages = 1 + len(lengths) * max_pages
    q = rng.randn(len(lengths), heads, dim).astype(np.float32)
    pool = rng.randn(n_pages, 3, page, heads, 2 * dim).astype(np.float32)
    table = np.full((len(lengths), max_pages), SCRATCH_PAGE, np.int32)
    ids = iter(rng.permutation(n_pages - 1) + 1)
    for i, ln in enumerate(lengths):
        n = -(-int(ln) // page)
        table[i, :n] = [next(ids) for _ in range(n)]
    table[3, 0] = table[7, 0]     # shared with the full row
    return q, pool, table, lengths


@pytest.mark.parametrize("max_pages,group", [
    (4, 1), (4, 2), (6, 3), (8, 4),      # G divides the table's width
    (5, 2), (7, 3), (9, 4), (5, 4),      # it does not: a short last group
    (3, 3), (8, 8)])                     # one group: G = max_pages
def test_decode_attention_page_groups(max_pages, group, monkeypatch):
    """The Pallas kernel with G pages a grid step agrees with numpy and
    with the XLA gather at every length a group boundary can meet."""
    from mxnet_tpu.ops.flash_attention import _decode_attention_xla

    q, pool, table, lengths = _grouped_case(max_pages, group)
    _set_page_group(monkeypatch, pool, max_pages, group)
    live = lengths > 0
    for layer in (0, 2):
        out = np.asarray(_paged_impl("pallas")(q, pool, layer, table,
                                               lengths))
        assert out.shape == q.shape and np.all(np.isfinite(out))
        want = _paged_reference(q, pool, layer, table, lengths)
        np.testing.assert_allclose(out[live], want[live], rtol=2e-5,
                                   atol=2e-5)
        xla = np.asarray(_decode_attention_xla(
            q, pool, layer, table, lengths, 1.0 / math.sqrt(q.shape[-1])))
        np.testing.assert_allclose(out[live], xla[live], rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("pool_shape,max_pages,group", [
    ((1025, 24, 16, 16, 128), 64, 8),      # gpt2m-serve-closed
    ((1025, 24, 16, 16, 128), 5, 5),       # a table narrower than that
    ((1025, 2, 16, 12, 128), 128, 8),      # 12 heads pad to 16 sublanes
    ((1025, 2, 16, 8, 128), 128, 16),
    ((1025, 2, 16, 8, 64), 128, 16),       # 64 lanes pad to 128
    ((9, 3, 4, 2, 16), 4, 4),              # tiny: the whole table
    ((4, 1, 128, 16, 128), 3, 1),          # a 1 MiB page: alone
    ((4, 1, 256, 16, 128), 3, 1),          # larger than the budget: still 1
    ((4, 1, 64, 16, 128), 3, 2)])
def test_decode_page_group_follows_the_shapes(pool_shape, max_pages, group):
    """G is the largest group whose pages fit 1 MiB of VMEM as float32
    tiles, at least 1, at most the page table's width."""
    from mxnet_tpu.ops.flash_attention import decode_page_group

    assert decode_page_group(pool_shape, max_pages) == group


@pytest.mark.parametrize("shape,dtype,env,interpret,path", [
    ((1025, 24, 16, 16, 128), "float32", "auto", False, "pallas"),
    ((1025, 2, 16, 12, 128), "float32", "auto", False, "pallas"),
    ((1025, 2, 16, 25, 128), "float32", "auto", False, "pallas"),
    ((1025, 2, 16, 16, 128), "bfloat16", "auto", False, "pallas"),
    ((1025, 2, 16, 12, 128), "bfloat16", "auto", False, "xla"),   # H % 8
    ((1025, 2, 16, 16, 64), "float32", "auto", False, "xla"),     # 64 lanes
    ((1025, 2, 16, 16, 128), "int8", "auto", False, "xla"),
    ((1025, 2, 16, 16, 128), "float32", "auto", True, "xla"),     # off-TPU
    ((1025, 2, 16, 16, 64), "float32", "pallas", True, "pallas"),  # named
    ((1025, 24, 16, 16, 128), "float32", "xla", False, "xla")])
def test_auto_takes_the_kernel_only_where_it_can_copy_whole_pages(
        shape, dtype, env, interpret, path, monkeypatch):
    """``auto`` on a TPU is the Pallas kernel for a pool whose ``(H, 2D)``
    rows fill whole tiles (what Mosaic can slice out of HBM for the
    kernel's own copies) and the XLA gather for any other; a path named
    outright is taken as named."""
    import jax

    from mxnet_tpu.ops import flash_attention

    monkeypatch.setenv("MXNET_DECODE_ATTN", env)
    monkeypatch.setattr(flash_attention, "_use_interpret", lambda: interpret)
    pool = jax.ShapeDtypeStruct(shape, dtype)
    assert flash_attention.decode_attention_impl(pool) == path


def test_decode_attention_one_page_a_grid_step_at_the_real_budget():
    """A geometry whose page fills the budget alone (1 MiB, float32) runs
    with G = 1: a page a grid step, as before pages came in groups."""
    rng = np.random.RandomState(11)
    page, heads, dim = 128, 16, 64
    q = rng.randn(3, heads, dim).astype(np.float32)
    pool = rng.randn(4, 1, page, heads, 2 * dim).astype(np.float32)
    table = np.array([[1, 2, 3], [3, SCRATCH_PAGE, SCRATCH_PAGE],
                      [SCRATCH_PAGE] * 3], np.int32)
    lengths = np.array([3 * page, 5, 0], np.int32)
    out = np.asarray(_paged_impl("pallas")(q, pool, 0, table, lengths))
    want = _paged_reference(q, pool, 0, table, lengths)
    np.testing.assert_allclose(out[:2], want[:2], rtol=2e-5, atol=2e-5)


def _engine_with_random_pool(lm):
    import jax.numpy as jnp
    eng = DecodeEngine(lm, slots=4, page_size=8, num_pages=16,
                       prompt_buckets=[8, 16])
    rng = np.random.RandomState(5)
    before = rng.randn(*eng.kv.shape).astype(np.float32)
    eng.kv = jnp.asarray(before)
    return eng, before


def test_step_writes_one_row_per_slot_and_layer(lm):
    """A decode step's KV write lands on row ``(page, layer, offset)`` of
    each slot, for every layer, K and V halves both — and every other
    element of the pool, all layers, stays bit-identical."""
    eng, before = _engine_with_random_pool(lm)
    dim = eng.cfg["head_dim"]
    positions = np.array([3, 8, 21, 0], np.int32)
    tables = np.full((4, eng.max_pages), SCRATCH_PAGE, np.int32)
    tables[0, :1] = [5]
    tables[1, :2] = [2, 9]
    tables[2, :3] = [4, 7, 11]
    lengths = np.array([4, 9, 22, 0], np.int32)   # slot 3 inactive
    eng.step(np.array([1, 2, 3, 0], np.int32), positions, tables, lengths,
             np.zeros((4,), np.float32))
    after = np.asarray(eng.kv)
    assert after.shape == before.shape == (16, 2, 8, 4, 2 * dim)
    written = np.zeros(before.shape[:3], bool)       # (page, layer, offset)
    for slot, pos in enumerate(positions):
        written[tables[slot, pos // 8], :, pos % 8] = True
    np.testing.assert_array_equal(after[~written], before[~written])
    live = [(5, 3), (9, 0), (11, 5)]                 # the active slots' rows
    for page, off in live:
        for layer in range(2):
            row, old = after[page, layer, off], before[page, layer, off]
            assert not np.any(row[:, :dim] == old[:, :dim])   # K written
            assert not np.any(row[:, dim:] == old[:, dim:])   # V written
    # layer 0 and layer 1 hold different rows: no layer was written twice
    assert not np.array_equal(after[5, 0, 3], after[5, 1, 3])


def test_prefill_writes_whole_pages_of_every_layer(lm):
    """Prefill scatters the prompt's K and V into its own pages, every
    layer's, and nowhere else; what it wrote is what ``lm_prefill``
    computed, position by position."""
    eng, before = _engine_with_random_pool(lm)
    dim = eng.cfg["head_dim"]
    prompt = np.arange(1, 12, dtype=np.int32)        # 11 tokens, bucket 16
    eng.prefill(prompt, [6, 3])
    after = np.asarray(eng.kv)
    untouched = np.ones(16, bool)
    untouched[[6, 3]] = False
    np.testing.assert_array_equal(after[untouched], before[untouched])
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = prompt
    _logits, k, v = lm_prefill(eng.cfg, decode_params(lm), padded)
    for layer in range(2):
        got = np.concatenate([after[6, layer], after[3, layer]], 0)
        np.testing.assert_allclose(got[:11, :, :dim],
                                   np.asarray(k)[layer, 0, :11],
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got[:11, :, dim:],
                                   np.asarray(v)[layer, 0, :11],
                                   rtol=1e-6, atol=1e-6)


def test_engine_greedy_matches_dense_reference(stack, lm):
    """The paged two-program engine is bitwise-identical to a dense
    full-forward-per-token reference (no paging, no batching)."""
    _eng, sched, _srv, _cli = stack
    cfg, params = decode_config(lm), decode_params(lm)
    prompt = [1, 2, 3, 4, 5]
    got = list(sched.generate(np.asarray(prompt, np.int32),
                              max_new_tokens=6))
    toks, ref = list(prompt), []
    for _ in range(6):
        logits, _k, _v = lm_prefill(
            cfg, params, np.asarray([toks], np.int32))
        nxt = int(np.argmax(np.asarray(logits[0, len(toks) - 1])))
        ref.append(nxt)
        toks.append(nxt)
    assert got == ref


@pytest.mark.parametrize("group", [1, 3, 8])
def test_engine_through_the_grouped_kernel_serves_the_dense_tokens(
        lm, group, monkeypatch):
    """The engine's step through the Pallas kernel (interpreted here) with
    1, 3 (which does not divide the 8-page table) or all 8 pages a grid
    step serves the dense reference's greedy tokens, for contexts that end
    inside a group's first page and that run across groups; the counter
    names the group and the step's grid steps."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")
    eng = DecodeEngine(lm, slots=2, page_size=8, num_pages=17,
                       prompt_buckets=[8, 32])
    _set_page_group(monkeypatch, eng.kv, eng.max_pages, group)
    sched = DecodeScheduler(eng, max_new_tokens=4)
    cfg, params = decode_config(lm), decode_params(lm)
    try:
        for prompt in ([5, 4, 3], list(range(1, 24))):
            got = list(sched.generate(np.asarray(prompt, np.int32),
                                      max_new_tokens=4))
            toks = list(prompt)
            for _ in range(4):
                logits, _k, _v = lm_prefill(
                    cfg, params, np.asarray([toks], np.int32))
                toks.append(int(np.argmax(
                    np.asarray(logits[0, len(toks) - 1]))))
            assert got == toks[len(prompt):]
    finally:
        # an idle scheduler left running records spans into whichever later
        # test of this worker turns obs on (tests/test_obs.py)
        sched.close()
    assert eng.stats()["paged_kernel"] == {
        "page_group": group, "grid_steps": 2 * -(-8 // group) * 2}
    assert eng.stats()["moe_row_tile"] is None      # no routed experts
    assert eng.stats()["delta_rule"] is None        # no delta layers
    eng.pool.assert_baseline()


def test_concurrent_streams_bitwise_equal_sequential(stack):
    """Greedy decoding is invariant to batch composition: tokens never
    depend on which other streams share the step program."""
    _eng, sched, _srv, _cli = stack
    prompts = [np.array([1, 2, 3, 4, 5], np.int32),
               np.array([10, 11, 12], np.int32)]
    got = [None, None]

    def run(i):
        got[i] = list(sched.generate(prompts[i], max_new_tokens=6))

    threads = [threading.Thread(target=run, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    for i in (0, 1):
        assert got[i] == list(sched.generate(prompts[i],
                                             max_new_tokens=6)), i


# ---------------------------------------------------------------------------
# 5. streaming wire
# ---------------------------------------------------------------------------

def test_wire_stream_roundtrip_bitwise(stack):
    eng, sched, _srv, cli = stack
    toks = list(cli.generate([1, 2, 3, 4, 5], max_new_tokens=6))
    ref = list(sched.generate(np.array([1, 2, 3, 4, 5], np.int32),
                              max_new_tokens=6))
    assert toks == ref and len(toks) == 6
    assert cli.ready()  # a decode-only replica is ready
    assert "decode" in cli.stats()
    eng.pool.assert_baseline()


def test_wire_client_hangup_reclaims_pages(stack):
    eng, _sched, _srv, cli = stack
    gen = cli.generate([1, 2, 3], max_new_tokens=50)
    next(gen)
    gen.close()  # hang-up IS the cancel signal
    _wait(lambda: eng.pool.used() == 0, msg="server-side page reclaim")
    assert cli.ready()  # client reconnects transparently after the drop


def test_wire_deadline_is_typed_mid_stream(stack):
    eng, _sched, _srv, cli = stack
    # tiny deadline: sheds either at submit or mid-generation — both must
    # surface as DeadlineExceeded through the STREAM_ERROR frame
    with pytest.raises(DeadlineExceeded):
        for _ in cli.generate([1, 2, 3], max_new_tokens=60,
                              deadline_ms=2):
            pass
    _wait(lambda: eng.pool.used() == 0, msg="page reclaim after shed")


def test_wire_chaos_drop_request_retries_precommit(stack):
    _eng, sched, _srv, cli = stack
    chaos_rpc.configure([chaos_rpc.Rule("infer_stream", "drop_request",
                                        {1})])
    toks = list(cli.generate([1, 2, 3, 4, 5], max_new_tokens=6))
    assert toks == list(sched.generate(
        np.array([1, 2, 3, 4, 5], np.int32), max_new_tokens=6))


def test_wire_chaos_dup_is_drained_frame_aligned(stack):
    _eng, sched, _srv, cli = stack
    ref = list(sched.generate(np.array([1, 2, 3, 4, 5], np.int32),
                              max_new_tokens=6))
    chaos_rpc.configure([chaos_rpc.Rule("infer_stream", "dup", {1})])
    assert list(cli.generate([1, 2, 3, 4, 5], max_new_tokens=6)) == ref
    chaos_rpc.configure([])
    # the duplicate's echo was drained: the socket is still frame-aligned
    assert cli.ready()
    assert list(cli.generate([1, 2, 3, 4, 5], max_new_tokens=6)) == ref


def test_wire_draining_refuses_streams():
    eng = _FakeDecodeEngine(slots=2)
    sched = DecodeScheduler(eng, max_new_tokens=4)
    srv = ServeServer(engine=None, decode=sched, port=0)
    srv.start()
    cli = ServeClient("127.0.0.1", srv.port, retries=2)
    try:
        assert list(cli.generate([1, 2])) == _fake_seq([1, 2], 4)
        cli.drain()
        with pytest.raises(Draining):
            list(cli.generate([1, 2]))
        eng.pool.assert_baseline()
    finally:
        cli.close()
        srv.stop()


def test_fleet_stream_relay_failover_and_merged_timeline():
    scheds = []

    def factory():
        eng = _FakeDecodeEngine(slots=2)
        s = DecodeScheduler(eng, max_new_tokens=6)
        scheds.append(s)
        srv = ServeServer(engine=None, decode=s, port=0)
        srv.start()
        return srv

    # the probe must not beat the four requests below to the killed
    # replica, or nothing is left to fail over from: under a loaded
    # machine 0.2 s did (937 passed, this one failed, PR 28's first whole run)
    pool = ReplicaPool.local(factory, 2, probe_interval=1.0)
    pool.start()
    router = Router(pool, breaker_cooldown=0.3)
    front = FleetServer(router, port=0)
    front.start()
    cli = ServeClient("127.0.0.1", front.port, retries=2)
    try:
        _wait(cli.ready, timeout=10, msg="fleet ready")
        ref = _fake_seq([1, 2, 3, 4, 5], 6)
        # relay is bitwise on BOTH replicas (round-robin)
        assert list(cli.generate([1, 2, 3, 4, 5])) == ref
        assert list(cli.generate([1, 2, 3, 4, 5])) == ref

        # one merged timeline: client root → front serve.rpc → replica
        # decode spans, all on ONE trace id
        obs.enable()
        root = obs_context.new_root(sampled=True)
        with obs_context.use(root):
            assert list(cli.generate([1, 2, 3, 4, 5])) == ref
        evs = obs.trace.drain()
        gen_tids = {(e.get("args") or {}).get("trace_id") for e in evs
                    if e["name"] == "decode.generate"}
        tok_tids = {(e.get("args") or {}).get("trace_id") for e in evs
                    if e["name"] == "decode.token"}
        rpc_tids = {(e.get("args") or {}).get("trace_id") for e in evs
                    if e["name"] == "serve.rpc"
                    and (e.get("args") or {}).get("trace_id")}
        assert gen_tids == {root.trace_id}
        assert tok_tids == {root.trace_id}
        assert root.trace_id in rpc_tids
        assert any(e["name"] == "fleet.route_stream" for e in evs)
        obs.disable()

        # failover happens only BEFORE the first token is committed
        pool.kill(0)
        ok = 0
        deadline = time.monotonic() + 10
        while ok < 4 and time.monotonic() < deadline:
            try:
                assert list(cli.generate([7, 8, 9],
                                         max_new_tokens=4)) == \
                    _fake_seq([7, 8, 9], 4)
                ok += 1
            except ServeError:
                time.sleep(0.1)
        assert ok == 4
        assert router.failovers >= 1
    finally:
        cli.close()
        front.stop()
        pool.stop()
    for s in scheds:
        assert s.engine.pool.used() == 0  # no page outlives its stream


# ---------------------------------------------------------------------------
# 6. process-level chaos + progcache warm start (subprocess legs)
# ---------------------------------------------------------------------------

_TINY_REPLICA = """\
import sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu.ndarray as nd
from mxnet_tpu.models.transformer import transformer_lm
from mxnet_tpu.serve.decode import DecodeEngine, DecodeScheduler
from mxnet_tpu.serve.server import ServeServer

lm = transformer_lm(vocab_size=61, units=16, hidden_size=32, num_layers=1,
                    num_heads=2, max_length=32, dropout=0.0)
lm.initialize()
lm(nd.zeros((1, 8)))
eng = DecodeEngine(lm, slots=2, page_size=8, num_pages=9,
                   prompt_buckets=[8])
sched = DecodeScheduler(eng, max_new_tokens=16)
srv = ServeServer(engine=None, decode=sched, port=0)
srv.start()
print("PORT %d" % srv.port, flush=True)
while True:
    time.sleep(1)
"""

_WARM_REPLICA = """\
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu.ndarray as nd
from mxnet_tpu.models.transformer import transformer_lm
from mxnet_tpu.serve.decode import DecodeEngine, DecodeScheduler

lm = transformer_lm(vocab_size=61, units=16, hidden_size=32, num_layers=1,
                    num_heads=2, max_length=32, dropout=0.0)
lm.initialize()
lm(nd.zeros((1, 8)))
eng = DecodeEngine(lm, slots=2, page_size=8, num_pages=9,
                   prompt_buckets=[8], progcache_dir=sys.argv[1])
fresh = eng.warmup()
# warmed programs must EXECUTE correctly, not just deserialize
sched = DecodeScheduler(eng, max_new_tokens=4)
toks = list(sched.generate(np.array([1, 2, 3], np.int32), max_new_tokens=4))
sched.close()
print(json.dumps({"fresh": fresh, "hits": eng.cache_hits,
                  "programs": len(eng.compile_log), "tokens": toks}))
"""


def _proc_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # XLA:CPU refuses executable export under the forced 8-device flag
    # the in-process conftest sets — strip it for subprocess replicas
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f)
    env.update(extra)
    return env


@pytest.mark.chaos
@pytest.mark.slow
def test_replica_sigkill_mid_stream_is_post_commit_error(tmp_path):
    """A replica SIGKILLed between token sends (`serve:mid_stream@3`)
    surfaces as the committed-stream error — never a silent retry that
    would interleave two generations."""
    script = tmp_path / "replica.py"
    script.write_text(_TINY_REPLICA)
    proc = subprocess.Popen(
        [sys.executable, str(script)], cwd=REPO,
        env=_proc_env(MXNET_CHAOS_KILL="serve:mid_stream@3"),
        stdout=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        assert line.startswith("PORT "), line
        port = int(line.split()[1])
        cli = ServeClient("127.0.0.1", port, timeout=120.0, retries=2)
        got = []
        try:
            with pytest.raises(ServeError,
                               match="stream broken after 2 tokens"):
                for tok in cli.generate([1, 2, 3], max_new_tokens=10):
                    got.append(tok)
            assert len(got) == 2  # exactly the tokens sent pre-kill
        finally:
            cli.close()
        proc.wait(timeout=10)
        assert proc.returncode == -9  # SIGKILL, not a clean exit
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


@pytest.mark.progcache
@pytest.mark.slow
def test_progcache_warmed_replica_zero_fresh_compiles(tmp_path):
    """Cold replica populates the shared program cache; a warm restart
    performs ZERO fresh XLA compiles (every program deserialized) and
    produces the same greedy tokens."""
    script = tmp_path / "warm.py"
    script.write_text(_WARM_REPLICA)
    cache_dir = tmp_path / "progcache"
    cache_dir.mkdir()

    def run():
        out = subprocess.run(
            [sys.executable, str(script), str(cache_dir)], cwd=REPO,
            env=_proc_env(), capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        import json
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["fresh"] == 2  # one prefill bucket + ONE step program
    if not list(cache_dir.glob("*.mxprog")):
        pytest.skip("backend refused AOT export; nothing persisted")
    warm = run()
    assert warm["fresh"] == 0
    assert warm["hits"] == 2
    assert warm["programs"] == 2
    assert len(warm["tokens"]) == 4


# ---------------------------------------------------------------------------
# 7. flagship: concurrent wire streams with churn (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_flagship_concurrent_streams_with_churn(stack):
    """8 concurrent wire clients over 4 slots — two hang up early, one
    carries a hopeless deadline — and every COMPLETED stream is bitwise
    equal to its solo sequential run, with zero residual pages and the
    program bound intact over the whole session."""
    eng, sched, srv, _cli = stack
    prompts = [np.arange(1, n + 1, dtype=np.int64) % 90 + 1
               for n in (3, 5, 7, 9, 11, 13, 4, 6)]
    results = [None] * 8

    def run(i):
        cli = ServeClient("127.0.0.1", srv.port, retries=2)
        try:
            if i in (2, 5):  # churn: hang up after 2 tokens
                gen = cli.generate(prompts[i], max_new_tokens=40)
                next(gen)
                next(gen)
                gen.close()
                results[i] = "cancelled"
            elif i == 7:  # churn: hopeless deadline
                try:
                    for _ in cli.generate(prompts[i], max_new_tokens=40,
                                          deadline_ms=2):
                        pass
                    results[i] = "finished"
                except DeadlineExceeded:
                    results[i] = "deadline"
            else:
                results[i] = list(cli.generate(prompts[i],
                                               max_new_tokens=6))
        finally:
            cli.close()

    threads = [threading.Thread(target=run, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert results[2] == results[5] == "cancelled"
    assert results[7] == "deadline"
    for i in (0, 1, 3, 4, 6):
        ref = list(sched.generate(prompts[i], max_new_tokens=6))
        assert results[i] == ref, i
    _wait(lambda: eng.pool.used() == 0, msg="full page reclaim")
    assert TraceLinter().check_decode_engine(eng) == []
    st = sched.stats()
    assert st["shed"] == sum(st["shed_by_reason"].values())
