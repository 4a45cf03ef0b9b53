"""Cluster chaos: the fleet under crashes, dropped frames, stale routes.

The cluster-front invariants (core/README.md) under injected faults:

* **takeover is invisible**: a coordinator crash mid-pagination re-plans
  the token on a new worker at the pinned snapshot, and the remaining
  pages are **bit-identical** to the no-crash stream (MVCC replay, not
  best-effort resume);
* **delivery is at-least-once, effects exactly-once**: dropped request
  *and* dropped response frames are retransmitted under one ``rid`` and
  absorbed by the coordinator's rid cache — one admission, never two;
* **ownership is authoritative**: a stale SLB view routes a continuation
  to the wrong coordinator, which must bounce (``WRONG_OWNER``) rather
  than answer from state it does not own.

Deterministic schedules pin each path; the hypothesis sweep then asserts
the pagination stream is schedule-independent — any mix of drops, stale
routes, and one crash converges to the identical row stream.
"""
import numpy as np
import pytest

from repro.core.faults import FaultInjector
from repro.core.query.executor import QueryCaps
from repro.core.recovery import FastRestartCache
from repro.launch.cluster import A1Frontend

from test_backend_parity import q_chain
from test_serve import SEL, busy_db, full_rows

CAPS = QueryCaps(frontier=128, expand=512, results=8)


def mk_fleet(db, n=3, **kw):
    """A fleet over its own rehydrated copy of ``db``: an inproc fleet
    serves and writes the very store it is given, and the module fixture
    is shared by many fleets."""
    kw.setdefault("caps", CAPS)
    kw.setdefault("page_size", 2)
    cache = FastRestartCache()
    cache.hold("fixture", db)
    return A1Frontend(cache.restart("fixture"), n, **kw)


def paginate(fe, on_page=None):
    """Drain one paged select; returns the ordered row stream."""
    page, tok = fe.select_paged(SEL)
    got, pages = list(page), 0
    while tok is not None and pages < 60:
        pages += 1
        if on_page is not None:
            on_page(pages, tok)
        page, tok = fe.next_page(tok)
        got.extend(int(x) for x in page)
    assert tok is None
    return [int(x) for x in got]


@pytest.fixture(scope="module")
def chaos_db():
    return busy_db()


@pytest.fixture(scope="module")
def clean_stream(chaos_db):
    """The no-fault pagination stream — the bit-identity oracle."""
    with mk_fleet(chaos_db) as fe:
        return paginate(fe)


# ---------------------------------------------------------------------------
# deterministic schedules
# ---------------------------------------------------------------------------

def test_takeover_mid_pagination_is_bit_identical(chaos_db, clean_stream):
    """Kill the owning coordinator after the first page: the takeover
    replays at the pinned snapshot and the FULL stream — including every
    page served after the crash — matches the no-crash stream exactly."""
    with mk_fleet(chaos_db) as fe:
        killed = []

        def crash_once(pages, tok):
            if pages == 1:
                fe.kill_worker(fe._tokmeta[tok]["cid"])
                killed.append(fe._tokmeta[tok]["cid"])

        got = paginate(fe, on_page=crash_once)
        assert got == clean_stream                  # ordered, bit-identical
        assert fe.stats["takeovers"] == 1
        assert fe.stats["worker_kills"] == 1
        assert not fe.db.active_query_ts            # pin released at the end
        assert sorted(got) == full_rows(fe.db, SEL)


def test_crash_with_inflight_queries_rescues_them(chaos_db):
    """Queries queued on the dead coordinator re-route with their
    remaining budget; every admitted id still terminates in one result."""
    with mk_fleet(chaos_db, read_batch=64) as fe:    # stays queued
        pubs = [fe.submit_query(q_chain(i % 3), budget_ms=1e6)
                for i in range(6)]
        owners = {fe._qidmeta[p]["cid"] for p in pubs}
        victim = sorted(owners)[0]
        n_victim = sum(1 for p in pubs if fe._qidmeta[p]["cid"] == victim)
        assert n_victim >= 1
        fe.kill_worker(victim)
        fe.flush()
        for i, p in enumerate(pubs):
            row = fe.query_result(p)
            solo = fe.db.query([q_chain(i % 3)], caps=CAPS)
            assert row is not None and row["status"] == "OK"
            assert row["count"] == int(solo.counts[0])
        assert fe.stats["rescued_queries"] == n_victim


def test_crash_site_kills_route_target_and_fails_over(chaos_db):
    """``cluster.worker.crash``: the target dies as the frame leaves; the
    SLB fails over to an alive coordinator in the same submit."""
    with mk_fleet(chaos_db) as fe:
        fe.db.faults = FaultInjector(0).inject(
            "cluster.worker.crash", action="race", times=(0,))
        pub = fe.submit_query(q_chain(0), budget_ms=1e6)
        fe.flush()
        row = fe.query_result(pub)
        solo = fe.db.query([q_chain(0)], caps=CAPS)
        assert row["status"] == "OK"
        assert row["count"] == int(solo.counts[0])
        assert fe.stats["worker_kills"] == 1
        assert len(fe._alive()) == 2


@pytest.mark.parametrize("drop_visit", [0, 1])
def test_dropped_frames_retransmit_idempotently(chaos_db, drop_visit):
    """``transport.drop`` on the request frame (visit 0: handler never
    ran) and on the response frame (visit 1: handler DID run — duplicate
    delivery) both end in exactly one admission under one ``rid``."""
    with mk_fleet(chaos_db, n=1, read_batch=1) as fe:
        fe.db.faults = FaultInjector(5).inject(
            "transport.drop", action="race", times=(drop_visit,))
        pub = fe.submit_query(q_chain(0), budget_ms=1e6)
        assert fe.stats["retransmits"] == 1
        row = fe.query_result(pub)
        solo = fe.db.query([q_chain(0)], caps=CAPS)
        assert row["status"] == "OK"
        assert row["count"] == int(solo.counts[0])
        st = fe.cluster_stats()
        assert st["workers"][0]["admitted"] == 1     # exactly-once effect
        assert st["frontend"]["frames_dropped"] == 1


def test_stale_route_storm_bounces_every_frame_to_the_owner(chaos_db,
                                                            clean_stream):
    """Every continuation frame first lands on a WRONG coordinator (stale
    SLB view, prob=1).  The receiver bounces by ownership stamp and the
    re-route serves the identical stream — the wrong worker never answers
    from state it does not own."""
    with mk_fleet(chaos_db) as fe:
        fe.db.faults = FaultInjector(3).inject(
            "cluster.route.stale", action="race", prob=1.0)
        got = paginate(fe)
        assert got == clean_stream
        assert fe.stats["stale_routes"] == fe.stats["continuation_routes"]
        assert fe.stats["stale_routes"] >= 2
        assert fe.stats["takeovers"] == 0


# ---------------------------------------------------------------------------
# any-schedule sweep
# ---------------------------------------------------------------------------

try:        # the deterministic schedules above run without hypothesis
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # CI installs it; local runs skip
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    seeds = st.integers(0, 2**16)
    crashes = st.integers(0, 6)
    drops = st.floats(0.0, 0.25)
    stales = st.floats(0.0, 1.0)
    checks = [HealthCheck.too_slow]
else:                                     # keep the decorators importable
    def given(**kw):
        return lambda fn: fn

    def settings(**kw):
        return lambda fn: fn
    seeds = crashes = drops = stales = checks = None


@pytest.mark.skipif(not HAVE_HYPOTHESIS,
                    reason="any-schedule sweep needs hypothesis (CI has it)")
@settings(max_examples=10, deadline=None, suppress_health_check=checks)
@given(seed=seeds, crash_after=crashes, drop_prob=drops,
       stale_prob=stales)
def test_any_schedule_pagination_converges(chaos_db, clean_stream, seed,
                                           crash_after, drop_prob,
                                           stale_prob):
    """Any seeded mix of frame drops, stale routes, and one mid-stream
    coordinator crash yields the SAME ordered row stream as the clean
    run.  ``max_fires`` bounds the drop storm so retransmits always
    converge (an unbounded adversary could drop every frame forever —
    that is an availability loss, not a correctness one)."""
    with mk_fleet(chaos_db) as fe:
        fe.db.faults = (
            FaultInjector(seed)
            .inject("transport.drop", action="race", prob=drop_prob,
                    max_fires=6)
            .inject("cluster.route.stale", action="race", prob=stale_prob))

        def maybe_crash(pages, tok):
            if pages == crash_after:
                fe.kill_worker(fe._tokmeta[tok]["cid"])

        got = paginate(fe, on_page=maybe_crash)
        assert got == clean_stream
