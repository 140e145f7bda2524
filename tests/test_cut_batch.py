"""A batch wider than the frontier holds is served as the waves of one
ticket (``DeviceCheckEngine.submit`` / ``_cut``, the rule in engine/wave.py):
BASELINE config #4's own request, a BatchCheck of 10,000 mixed rows,
against the oracle row for row, on the CPU.

One seeded Drive-style graph and one cascade engine a module, with the
depth and the general tier's levels cut so that XLA:CPU compiles the two
programs of a 256-row wave in seconds and runs a wave in tens of
milliseconds: the frontier holds 256 rows (``wave_cap``), so the
10,000-row batch is forty waves.  It is sent once, as a ColumnBlock (the
served path), and the cases read what it left; the other cases send a few
waves each.  A file of its own: xdist hands out whole files.
"""

import numpy as np
import pytest

from ketotpu.api.types import KetoAPIError, RelationTuple
from ketotpu.driver import Provider, Registry
from ketotpu.engine import fastpath as fp
from ketotpu.engine import wave as wv
from ketotpu.engine.coalesce import CoalescingEngine
from ketotpu.engine.columns import ColumnBlock
from ketotpu.engine.tpu import DeviceCheckEngine
from ketotpu.utils.synth import build_synth, synth_queries_mixed
from ketotpu.waveledger import WaveLedger

T = RelationTuple.from_string

DEPTH = 3
ENGINE_KW = dict(
    frontier=1280, arena=2560, cap=512, gen_arena=512, vcap=256,
    gen_levels=3, gen_levels_max=4, max_depth=DEPTH,
)
CAP = 256
ROWS = 10_000
COUNTERS = ("tickets", "ticket_waves", "dispatches", "fallbacks", "retries",
            "device_failures")


def counters(dev) -> dict:
    got = {k: getattr(dev, k) for k in COUNTERS}
    got.update(dev.overflow_rows)
    got["check_cut"] = dev.phase_counts.get("check_cut", 0)
    return got


def moved(dev, before: dict) -> dict:
    return {k: v - before[k] for k, v in counters(dev).items()
            if v != before[k]}


@pytest.fixture(scope="module", autouse=True)
def one_schedule():
    """The worst-case level schedule throughout, as the benchmark's cells
    run: the demand-adapted one is a second set of programs to compile."""
    patch = pytest.MonkeyPatch()
    patch.setenv("KETO_NO_ADAPTIVE", "1")
    yield
    patch.undo()


@pytest.fixture(scope="module")
def setup():
    graph = build_synth(n_users=64, n_groups=8, n_folders=32, n_docs=128)
    dev = DeviceCheckEngine(graph.store, graph.manager, **ENGINE_KW)
    dev.snapshot()
    assert CAP == wv.wave_cap(
        lambda q, f, a: fp.level_schedule(q, f, a, DEPTH),
        dev.frontier, dev.arena)
    return graph, dev


def oracle(dev, queries):
    return [dev.oracle.check_is_member(q) for q in queries]


@pytest.fixture(scope="module")
def served(setup):
    """The 10,000-row batch through ``batch_check_block``, once."""
    graph, dev = setup
    queries = synth_queries_mixed(graph, ROWS, seed=33)
    before = counters(dev)
    allowed, errs = dev.batch_check_block(ColumnBlock.from_tuples(queries))
    return queries, allowed, errs, moved(dev, before)


def test_10k_block_equals_the_oracle_row_for_row(setup, served):
    _, dev = setup
    queries, allowed, errs, _ = served
    assert allowed.dtype == bool and len(allowed) == ROWS
    assert allowed.tolist() == oracle(dev, queries)
    assert 0 < allowed.sum() < ROWS
    assert errs == {}


def test_10k_block_is_forty_waves_and_none_on_the_host(served):
    """One ticket, cut once, ROWS / CAP waves rounded up, each a dispatch;
    no row overflowed, was retried or went to the oracle."""
    *_, delta = served
    waves = -(-ROWS // CAP)
    assert delta == {"tickets": 1, "ticket_waves": waves,
                     "dispatches": waves, "check_cut": 1}


def test_batch_check_of_a_tuple_list_is_cut_the_same(setup):
    graph, dev = setup
    queries = synth_queries_mixed(graph, 10 * CAP + 40, seed=34)
    before = counters(dev)
    got = dev.batch_check(queries)
    assert got == oracle(dev, queries)
    assert moved(dev, before) == {
        "tickets": 1, "ticket_waves": 11, "dispatches": 11, "check_cut": 1}


def test_a_ticket_is_equal_waves_in_request_order(setup):
    """What ``submit`` leaves in the ticket: every row in one wave, the
    waves one row apart and padded alike, the AND/NOT rows dealt evenly."""
    graph, dev = setup
    queries = synth_queries_mixed(graph, 2 * CAP + 3, seed=35)
    ticket = dev.submit(queries)
    assert ticket.failure is None and len(ticket.waves) == 3
    rows = [r for r, _ in ticket.chunks]
    assert sorted(np.concatenate(rows).tolist()) == list(range(len(queries)))
    assert [len(r) for r in rows] == [172, 172, 171]
    assert [[queries[i] for i in r] for r in rows] == [
        c for _, c in ticket.chunks]
    assert {w.qpad for w in ticket.waves} == {256}
    gens = [int(w.general.sum()) for w in ticket.waves]
    assert max(gens) - min(gens) <= 1 and min(gens) > 0
    assert {w.gen_like for w in ticket.waves} == {max(gens)}
    assert dev.collect(ticket) == oracle(dev, queries)


@pytest.mark.parametrize("n", [1, CAP])
def test_up_to_the_cap_one_wave_untouched(setup, n):
    """No cut, no span, the caller's own queries as the wave's, padded by
    the wave's own size: what ``submit`` did before there was a cut."""
    graph, dev = setup
    block = ColumnBlock.from_tuples(synth_queries_mixed(graph, n, seed=36))
    before = counters(dev)
    ticket = dev.submit(block)
    [(rows, chunk)], [wave] = ticket.chunks, ticket.waves
    assert chunk is block and rows.tolist() == list(range(n))
    assert (wave.n, wave.qpad, wave.gen_like) == (n, 256, 0)
    allowed, errs = dev.collect(ticket, errs={})
    assert allowed.tolist() == oracle(dev, [block[i] for i in range(n)])
    assert moved(dev, before) == {
        "tickets": 1, "ticket_waves": 1, "dispatches": 1}


def test_a_typed_error_lands_in_its_own_row_of_any_wave(setup):
    """The columnar contract across a cut: an undeclared relation is the
    oracle's typed error in ``errs[row]``, by the batch's row, and every
    other row is answered."""
    graph, dev = setup
    queries = synth_queries_mixed(graph, 2 * CAP + 88, seed=37)
    bad = (0, 300, len(queries) - 1)
    for i in bad:
        queries[i] = T(f"Doc:d{i % 7}#nope@u1")
    before = counters(dev)
    allowed, errs = dev.batch_check_block(ColumnBlock.from_tuples(queries))
    assert sorted(errs) == list(bad)
    assert all(isinstance(e, KetoAPIError) for e in errs.values())
    good = [i for i in range(len(queries)) if i not in bad]
    assert allowed[good].tolist() == oracle(dev, [queries[i] for i in good])
    assert moved(dev, before) == {
        "tickets": 1, "ticket_waves": 3, "dispatches": 3, "check_cut": 1,
        "fallbacks": len(bad)}
    # without ``errs`` the first typed error aborts the batch, as ever
    with pytest.raises(KetoAPIError):
        dev.batch_check(queries)


@pytest.mark.parametrize("half", ["submit", "collect"])
def test_a_failure_in_one_wave_is_answered_as_collect_documents(
        setup, monkeypatch, half):
    """Any exception but a typed one, in either half, in any wave: a
    device failure, and the whole batch is answered on the oracle."""
    graph, dev = setup
    queries = synth_queries_mixed(graph, 2 * CAP + 5, seed=38)
    name = "_dispatch" if half == "submit" else "_collect"
    real, calls = getattr(dev, name), []

    def second_fails(*a, **kw):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("the second wave's " + half)
        return real(*a, **kw)

    monkeypatch.setattr(dev, name, second_fails)
    before = counters(dev)
    ticket = dev.submit(queries)
    assert (ticket.failure is not None) == (half == "submit")
    allowed, errs = dev.collect(ticket, errs={7: KetoAPIError("stale")})
    assert allowed.tolist() == oracle(dev, queries) and errs == {}
    delta = moved(dev, before)
    assert delta["device_failures"] == 1
    assert delta["fallbacks"] == len(queries)
    assert delta["ticket_waves"] == 3


def test_overflow_rows_are_counted_at_collect_by_tier(setup, monkeypatch):
    """The cascade's counters: rows a first pass left unanswered for want
    of capacity, before the retry that may yet answer them."""
    graph, dev = setup
    queries = synth_queries_mixed(graph, 64, seed=39)
    general = np.array([q.relation == "edit" for q in queries])
    real_fast, real_gen = dev._fast_bits, wv.decode_general

    def fast_over(res, k):
        f = real_fast(res, k)
        f.over[:] = ~f.found  # every unanswered fast row reads overflowed
        return f

    def gen_over(words):
        g = real_gen(words)
        return wv.GeneralBits(g.code, np.ones_like(g.over), g.dirty)

    monkeypatch.setattr(dev, "_fast_bits", fast_over)
    before = counters(dev)
    wave = dev._dispatch(queries, 0)
    f = real_fast(wave.fast, len(queries))
    unfound = int((~f.found & ~general).sum())
    monkeypatch.setattr(wv, "decode_general", gen_over)
    dev._collect(wave, retry=False)
    delta = moved(dev, before)
    assert delta["fast"] == unfound > 0
    assert delta["general"] == int(general.sum()) > 0


def test_the_scrape_carries_tickets_waves_and_overflow_rows():
    """The registry publishes the engine's counters as they stand."""
    reg = Registry(Provider({
        "namespaces": [{"name": "Doc"}],
        "engine": {"kind": "tpu", "coalesce_ms": 0},
    })).init()
    try:
        eng = reg.check_engine()
        eng = getattr(eng, "inner", eng)
        eng.tickets += 3
        eng.ticket_waves += 21
        eng.overflow_rows["fast"] += 5
        eng.overflow_rows["general"] += 2
        reg.sample_engine_metrics()
        m = reg.metrics()
        assert m.get_gauge("keto_engine_tickets_total") == 3
        assert m.get_gauge("keto_engine_ticket_waves_total") == 21
        assert m.get_gauge(
            "keto_engine_overflow_rows_total", tier="fast") == 5
        assert m.get_gauge(
            "keto_engine_overflow_rows_total", tier="general") == 2
    finally:
        reg.close_engines()


def test_the_wave_ledger_records_ticket_waves(setup):
    """A block that rides a coalesced wave and outgrows the frontier: one
    ledger record, three device waves."""
    graph, dev = setup
    queries = synth_queries_mixed(graph, 2 * CAP + 9, seed=40)
    led = WaveLedger(capacity=8)
    co = CoalescingEngine(dev, window=0.001, batch_max=4096, ledger=led)
    try:
        allowed, errs = co.check_block(ColumnBlock.from_tuples(queries))
    finally:
        co.close()
    assert allowed.tolist() == oracle(dev, queries) and errs == {}
    [record] = led.snapshot()
    assert record["block_items"] == len(queries)
    assert record["ticket_waves"] == 3
