"""Coalescer tests: concurrent single checks ride shared device dispatches
with unchanged per-query semantics (engine/coalesce.py)."""

import threading
import time

import numpy as np
import pytest

from ketotpu.api.types import BadRequestError, RelationTuple
from ketotpu.engine.coalesce import PLACES, CoalescingEngine
from ketotpu.engine.columns import ColumnBlock
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.utils.synth import build_synth, synth_queries
from ketotpu.waveledger import WaveLedger

T = RelationTuple.from_string


@pytest.fixture(scope="module")
def setup():
    graph = build_synth(n_users=64, n_groups=8, n_folders=32, n_docs=128)
    dev = DeviceCheckEngine(
        graph.store, graph.manager, frontier=2048, arena=4096, max_batch=512
    )
    dev.snapshot()
    return graph, dev


def test_concurrent_checks_coalesce_and_agree(setup):
    graph, dev = setup
    eng = CoalescingEngine(dev, window=0.02)
    queries = synth_queries(graph, 64, seed=9)
    want = [dev.oracle.check_is_member(q) for q in queries]
    got = [None] * len(queries)

    def worker(i):
        got[i] = eng.check_is_member(queries[i])

    threads = [
        threading.Thread(target=worker, args=(i,))
        for i in range(len(queries))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want
    assert eng.coalesced == len(queries)
    # 64 concurrent singles must NOT cost 64 dispatches
    assert eng.waves < len(queries) / 4
    eng.close()


def test_error_isolation(setup):
    graph, dev = setup
    eng = CoalescingEngine(dev, window=0.02)
    good = synth_queries(graph, 4, seed=11)
    # undeclared relation on a configured namespace: typed client error
    bad = T("Doc:d0#nope@u1")
    results = {}
    errors = {}

    def check(i, q):
        try:
            results[i] = eng.check_is_member(q)
        except Exception as e:  # noqa: BLE001
            errors[i] = e

    threads = [
        threading.Thread(target=check, args=(i, q))
        for i, q in enumerate([*good, bad])
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == len(good)  # the good queries all answered
    assert isinstance(errors[len(good)], BadRequestError)
    eng.close()


def test_depth_groups_answer_independently(setup):
    graph, dev = setup
    eng = CoalescingEngine(dev, window=0.02)
    q = synth_queries(graph, 1, seed=13)[0]
    out = {}

    def check(d):
        out[d] = eng.check_is_member(q, d)

    threads = [threading.Thread(target=check, args=(d,)) for d in (0, 2, 4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for d in (0, 2, 4):
        assert out[d] == dev.oracle.check_is_member(q, d), d
    eng.close()


def test_passthrough_surface(setup):
    graph, dev = setup
    eng = CoalescingEngine(dev, window=0.001)
    qs = synth_queries(graph, 8, seed=15)
    assert eng.batch_check(qs) == dev.batch_check(qs)
    assert eng.max_depth == dev.max_depth  # attribute proxying
    eng.close()


def test_check_after_close_answers_directly(setup):
    graph, dev = setup
    eng = CoalescingEngine(dev, window=0.001)
    q = synth_queries(graph, 1, seed=17)[0]
    eng.close()
    assert eng.check_is_member(q) == dev.oracle.check_is_member(q)


def test_identical_concurrent_checks_share_one_slot():
    # hot-spot shield: N identical concurrent checks must occupy ONE batch
    # slot (the Zanzibar lock-table dedup) — the wave dispatches a batch of
    # length 1 and every caller gets the shared verdict
    class Recorder:
        def __init__(self):
            self.batches = []

        def batch_check(self, queries, depth=0):
            self.batches.append(list(queries))
            return [True] * len(queries)

    inner = Recorder()
    eng = CoalescingEngine(inner, window=0.1)
    q = T("Doc:d0#view@u1")
    n = 16
    got = []
    lock = threading.Lock()

    def worker():
        v = eng.check_is_member(q)
        with lock:
            got.append(v)

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == [True] * n
    # every dispatched batch is deduped: the identical checks never
    # occupy more than one slot per wave (thread-start timing may split
    # the herd over a couple of waves, but within a wave there is one)
    for batch in inner.batches:
        assert len(batch) == 1, batch
    total_slots = sum(len(b) for b in inner.batches)
    assert eng.singleflight_collapsed == n - total_slots
    assert eng.singleflight_collapsed > 0
    eng.close()


def test_followers_start_fresh_flight_after_wave(setup):
    # a check arriving AFTER its twin's wave was cut must not read a
    # settled slot: it starts a fresh flight and still answers correctly
    graph, dev = setup
    eng = CoalescingEngine(dev, window=0.001)
    q = synth_queries(graph, 1, seed=23)[0]
    want = dev.oracle.check_is_member(q)
    assert eng.check_is_member(q) == want
    assert eng.check_is_member(q) == want
    assert eng.singleflight_collapsed == 0
    eng.close()


def test_unexpected_error_raises_wave_without_serial_fallback():
    # advisor r2: a transient device failure must NOT degrade the wave to
    # per-query serial dispatches on the lone worker thread — it re-raises
    # to every caller (only typed KetoAPIError gets per-query isolation)
    class Boom:
        def __init__(self):
            self.calls = 0

        def batch_check(self, queries, depth=0):
            self.calls += 1
            raise RuntimeError("device lost")

    inner = Boom()
    eng = CoalescingEngine(inner, window=0.05)
    outcomes = []

    def worker():
        try:
            eng.check_is_member(T("d:x#r@u"))
            outcomes.append("no error")
        except RuntimeError:
            outcomes.append("runtime")
        except Exception:  # noqa: BLE001
            outcomes.append("wrong type")

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert outcomes == ["runtime"] * 8
    # one dispatch per wave, never one per query
    assert inner.calls < 8
    eng.close()


# -- the submit / collect pair (PR 32) ----------------------------------------


class TwoPhase:
    """A recording inner engine with the ``submit`` / ``collect`` pair.
    A ticket is ``(serial, queries)``; ``fail`` maps a serial to the half
    that raises for it ("submit" or "collect")."""

    def __init__(self, collect_s=0.0, fail=None, retry_ok=False,
                 row_errors=None, hold=None):
        self.collect_s = collect_s
        # ``hold``: a serial whose collect waits (10 s at most) until the
        # serial after it is submitted
        self.hold = hold
        self.submitted = {}  # serial -> threading.Event
        self.fail = dict(fail or {})
        self.retry_ok = retry_ok
        self.row_errors = dict(row_errors or {})
        self.lock = threading.Lock()
        self.serial = 0
        self.log = []  # (event, serial, thread ident)
        self.uncollected = 0
        self.most_uncollected = 0
        self.direct = []  # batches that came through batch_check

    def _note(self, event, serial):
        self.log.append((event, serial, threading.get_ident()))

    def _submitted(self, serial):
        with self.lock:
            return self.submitted.setdefault(serial, threading.Event())

    def submit(self, queries, depth=0):
        with self.lock:
            self.serial += 1
            serial = self.serial
            self.uncollected += 1
            self.most_uncollected = max(
                self.most_uncollected, self.uncollected)
            self._note("submit", serial)
            self.submitted.setdefault(serial, threading.Event()).set()
        if self.fail.get(serial) == "submit":
            raise RuntimeError(f"submit {serial} failed")
        return serial, queries

    def collect(self, ticket, errs=None):
        serial, queries = ticket
        if serial == self.hold:
            self._submitted(serial + 1).wait(10)
        time.sleep(self.collect_s)
        with self.lock:
            self.uncollected -= 1
            self._note("collected", serial)
        if self.fail.get(serial) == "collect":
            raise RuntimeError(f"collect {serial} failed")
        if errs is None:
            return [True] * len(queries)
        errs.update(self.row_errors)
        return np.ones(len(queries), bool), errs

    def batch_check(self, queries, depth=0):
        with self.lock:
            self.direct.append(list(queries))
        if not self.retry_ok:
            raise RuntimeError("retry failed")
        return [True] * len(queries)

    def batch_check_block(self, block, depth=0):
        return self.collect(self.submit(block, depth), errs={})


def _closed_loop(eng, callers, each, lead=None):
    """``callers`` threads, each sending ``each`` distinct checks one after
    the other; returns the outcomes (a verdict or the exception).  With
    ``lead``, the first caller starts alone and the others once ``lead()``
    returns."""
    out = {}

    def run(c):
        for k in range(each):
            try:
                out[c, k] = eng.check_is_member(T(f"d:o{c}#r@u{k}"))
            except Exception as e:  # noqa: BLE001
                out[c, k] = e

    threads = [threading.Thread(target=run, args=(c,)) for c in range(callers)]
    threads[0].start()
    if lead is not None:
        lead()
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads)
    return out


def test_next_wave_is_submitted_while_the_wave_before_is_collected():
    # closed-loop callers that share a wave stay in step, and a wave ahead
    # needs callers out of step: the first caller's wave goes alone, and
    # its collect is held until the others' wave is submitted, however
    # slowly a loaded host schedules the threads
    inner = TwoPhase(collect_s=0.02, hold=1)
    eng = CoalescingEngine(inner, window=0.001)
    out = _closed_loop(eng, callers=8, each=6,
                       lead=lambda: inner._submitted(1).wait(10))
    eng.close()
    assert list(out.values()) == [True] * 48
    assert inner.direct == []  # everything through the pair
    at = {(e, s): i for i, (e, s, _) in enumerate(inner.log)}
    ahead = [s for s in range(1, inner.serial)
             if at["submit", s + 1] < at["collected", s]]
    assert ahead, inner.log
    assert eng.waves_ahead >= len(ahead) > 0
    # one being collected, one staged, one held at put(): no fifth place
    assert 1 < inner.most_uncollected <= PLACES - 1
    assert eng._uncollected == 0


@pytest.mark.parametrize("pipeline", [True, False])
def test_which_thread_submits_and_which_collects(pipeline):
    inner = TwoPhase(collect_s=0.005)
    eng = CoalescingEngine(inner, window=0.001, pipeline=pipeline)
    out = _closed_loop(eng, callers=6, each=4)
    eng.close()
    assert list(out.values()) == [True] * 24
    submitters = {t for e, _, t in inner.log if e == "submit"}
    collectors = {t for e, _, t in inner.log if e == "collected"}
    assert len(submitters) == len(collectors) == 1
    if pipeline:
        assert submitters != collectors
    else:
        # one thread submits then collects: never a wave ahead
        assert submitters == collectors
        assert inner.most_uncollected == 1
        assert eng.waves_ahead == 0


def test_inner_engine_without_the_pair_is_served_through_batch_check():
    class Plain:
        def __init__(self):
            self.batches = []

        def batch_check(self, queries, depth=0):
            self.batches.append(list(queries))
            return [True] * len(queries)

    inner = Plain()
    eng = CoalescingEngine(inner, window=0.001)
    out = _closed_loop(eng, callers=4, each=3)
    eng.close()
    assert list(out.values()) == [True] * 12
    assert sum(len(b) for b in inner.batches) == 12
    assert eng.waves == len(inner.batches)
    assert eng.waves_ahead == 0


@pytest.mark.parametrize("half", ["submit", "collect"])
@pytest.mark.parametrize("retry_ok", [False, True])
def test_failed_half_reaches_its_own_wave_after_one_retry(half, retry_ok):
    # three waves of three callers, one after the other; the second fails
    inner = TwoPhase(fail={2: half}, retry_ok=retry_ok)
    eng = CoalescingEngine(inner, window=0.05)
    waves = []
    for w in range(3):
        got = {}

        def run(i, w=w, got=got):
            try:
                got[i] = eng.check_is_member(T(f"d:w{w}#r@u{i}"))
            except Exception as e:  # noqa: BLE001
                got[i] = e

        threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        waves.append([got[i] for i in range(3)])
    eng.close()
    assert inner.serial == 3, "the callers of a round did not share a wave"
    assert waves[0] == waves[2] == [True] * 3
    # ONE whole-wave retry, through batch_check, of that wave's rows alone
    assert [sorted(str(q) for q in b) for b in inner.direct] == [
        sorted(f"d:w1#r@u{i}" for i in range(3))]
    if retry_ok:
        assert waves[1] == [True] * 3
    else:
        assert [type(e) for e in waves[1]] == [RuntimeError] * 3


def test_typed_error_on_a_row_of_a_merged_block_lands_on_that_row():
    bad = BadRequestError("undeclared relation")
    inner = TwoPhase(row_errors={2: bad})
    eng = CoalescingEngine(inner, window=0.01, batch_max=64)
    block = ColumnBlock.from_tuples(
        [T(f"d:o#r@u{i}") for i in range(5)])
    verdicts, errs = eng.check_block(block)
    eng.close()
    assert errs == {2: bad}
    assert verdicts.tolist() == [True] * 5
    assert inner.direct == [] and inner.serial == 1
    assert eng.block_waves == 1


# -- the pair on the real engine (XLA:CPU) ------------------------------------


def _grant(graph, dev):
    """A ``Doc#viewers`` grant the graph does not hold: denied before it
    is written, allowed after."""
    tuples = graph.store.all_tuples()
    doc = next(t for t in tuples
               if t.namespace == "Doc" and t.relation == "viewers")
    users = sorted({str(t.subject) for t in tuples
                    if ":" not in str(t.subject)})
    for u in users:
        t = T(f"Doc:{doc.object}#viewers@{u}")
        if not dev.oracle.check_is_member(t):
            return t
    raise AssertionError("every user already views the doc")


def test_acknowledged_write_is_seen_with_waves_in_flight(setup):
    graph, dev = setup
    eng = CoalescingEngine(dev, window=0.001)
    queries = synth_queries(graph, 32, seed=31)
    stop = threading.Event()

    def load(k):
        i = k
        while not stop.is_set():
            eng.check_is_member(queries[i % len(queries)])
            i += 12

    threads = [threading.Thread(target=load, args=(k,)) for k in range(12)]
    for t in threads:
        t.start()
    grant = _grant(graph, dev)
    try:
        for _ in range(4):
            assert eng.check_is_member(grant) is False
            graph.store.write_relation_tuples(grant)
            # acknowledged before the Check is enqueued: the wave that
            # carries it takes its view after it was cut
            assert eng.check_is_member(grant) is True
            graph.store.delete_relation_tuples(grant)
    finally:
        stop.set()
        for t in threads:
            t.join(30)
        graph.store.delete_relation_tuples(grant)
        eng.close()
    assert not any(t.is_alive() for t in threads)
    assert eng.waves_ahead > 0  # the load kept a wave ahead


def test_projection_swap_between_submit_and_collect(setup):
    graph, dev = setup
    grant = _grant(graph, dev)
    queries = [*synth_queries(graph, 40, seed=37), grant]
    want = [dev.oracle.check_is_member(q) for q in queries]
    assert want[-1] is False
    ticket = dev.submit(queries)
    wave = ticket.waves[0]
    arrays, cursor = wave.arrays, wave.cursor
    graph.store.write_relation_tuples(grant)
    try:
        dev.refresh()  # a new projection, a newer cursor
        assert dev.batch_check([grant]) == [True]
        # the wave in flight answers from its own view, at its own cursor
        assert dev.collect(ticket) == want
        assert wave.arrays is arrays and wave.cursor == cursor
        assert dev._sync_view()[2] > cursor
    finally:
        graph.store.delete_relation_tuples(grant)
        dev.refresh()
    assert dev.batch_check(queries) == want


def test_ledger_phases_are_each_wave_s_own(setup):
    graph, dev = setup
    ledger = WaveLedger(capacity=512)
    eng = CoalescingEngine(dev, window=0.001, ledger=ledger)
    queries = synth_queries(graph, 96, seed=41)
    want = [dev.oracle.check_is_member(q) for q in queries]
    got = [None] * len(queries)
    before = {k: dev.phase_seconds.get(k, 0.0)
              for k in ("check_encode", "check_collect_sync")}

    def run(k):
        for i in range(k, len(queries), 12):
            got[i] = eng.check_is_member(queries[i])

    threads = [threading.Thread(target=run, args=(k,)) for k in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    eng.close()
    assert got == want
    records = ledger.snapshot()
    assert len(records) == eng.waves > 1
    assert eng.waves_ahead > 0  # waves overlapped: the case at stake
    for phase, was in before.items():
        ms = [r["phase_ms"].get(phase, 0.0) for r in records]
        assert all(v > 0 for v in ms), (phase, records)
        # had any record held another wave's seconds beside its own, the
        # records would add up to more than the engine spent
        spent = (dev.phase_seconds[phase] - was) * 1000.0
        assert sum(ms) == pytest.approx(spent, abs=0.002 * len(ms))
