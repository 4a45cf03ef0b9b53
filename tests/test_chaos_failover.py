"""Failover chaos: leases, epochs, and fleet-visible writes under crashes.

The membership/failover invariants (core/README.md) under injected faults:

* **acked commits are durable**: a write acknowledged ``COMMITTED``
  stays readable after the primary that committed it is killed — the
  election promotes a caught-up replica and reads keep answering;
* **exactly once**: a primary that crashes *after* commit but *before*
  the ack (``primary.crash.midwave``) never double-applies — the
  retransmit to the promoted primary resolves by rid to the ORIGINAL
  result, and the store holds the write exactly once;
* **no split-brain**: a deposed primary that missed its demote frame
  (partitioned zombie) is stopped at the commit-time fence — its staged
  wave answers ``ABORTED_FAILOVER`` and the store is untouched; frames
  stamped with an old configuration epoch bounce ``STALE_EPOCH``;
* **no silent drops**: every admitted write terminates with a definite
  answer — ``COMMITTED`` or a retryable abort, never a lost promise.

Deterministic schedules pin each path (kill, mid-wave crash, zombie
fence, lease expiry on a fake clock, forced primary expiry); the
hypothesis sweep then runs seeded mixes of writes, crashes, and
heartbeat loss and asserts the durability/exactly-once/no-split-brain
trio on every schedule.
"""
import numpy as np
import pytest

from repro.core.faults import FaultInjector
from repro.core.query.executor import QueryCaps
from repro.core.recovery import FastRestartCache
from repro.core.writes import CreateEdge
from repro.launch.cluster import A1Frontend

from test_backend_parity import q_chain
from test_serve import SEL, busy_db, full_rows

CAPS = QueryCaps(frontier=128, expand=512, results=64)
COUNT_DOC = q_chain(323, direction="in")          # films of actor 323


def mk_fleet(db, n=3, **kw):
    """A fleet over its own rehydrated copy of ``db``: an inproc fleet
    serves and writes the very store it is given, and the module fixture
    is shared by many fleets."""
    kw.setdefault("caps", CAPS)
    cache = FastRestartCache()
    cache.hold("fixture", db)
    return A1Frontend(cache.restart("fixture"), n, **kw)


def unlinked_films(db, actor_key=323):
    """(actor_gid, [film gids not yet linked to the actor]) — each chaos
    write links one more film, so edge creation never collides."""
    a_gid, ok = db.lookup_vertex("actor", actor_key)
    assert ok
    linked = set(full_rows(db, SEL))
    films = []
    for k in range(100, 120):
        g, found = db.lookup_vertex("film", k)
        if found and g not in linked:
            films.append(int(g))
    assert films, "busy_db should leave some films unlinked"
    return int(a_gid), films


def fleet_count(fe, doc=COUNT_DOC, tries=200):
    """Count query through the SLB (counts ignore the results cap)."""
    pub = fe.submit_query(doc, budget_ms=1e6)
    fe.flush()
    for _ in range(tries):
        r = fe.query_result(pub)
        if r is not None:
            assert not r.get("failed"), r
            return int(r["count"])
        fe.flush()
    raise AssertionError("query never completed")


def do_write(fe, ops, tries=200):
    """Submit one write and poll it to a terminal answer."""
    pub = fe.submit_write(ops)
    for _ in range(tries):
        r = fe.write_result(pub)
        if r is not None:
            return r
        fe.flush()
    raise AssertionError("write never terminated")


@pytest.fixture(scope="module")
def chaos_db():
    return busy_db()


# ---------------------------------------------------------------------------
# deterministic schedules
# ---------------------------------------------------------------------------

def test_primary_kill_preserves_acked_commit(chaos_db):
    """Durability: an acked commit survives the death of the primary that
    committed it, and the promoted replica keeps serving writes."""
    with mk_fleet(chaos_db, write_batch=1) as fe:
        a_gid, films = unlinked_films(fe.db)
        base = fleet_count(fe)
        r = do_write(fe, [CreateEdge(films[0], a_gid, "film.actor")])
        assert r["status"] == "COMMITTED"

        fe.kill_worker(0)                         # the write-primary
        view = fe.membership.view()
        assert view["leases"][0]["state"] == "evicted"
        assert view["epoch"] == 2 and view["primary"] == 1
        assert fe.stats["failovers"] == 1
        assert fleet_count(fe) == base + 1        # the ack was not a lie

        r2 = do_write(fe, [CreateEdge(films[1], a_gid, "film.actor")])
        assert r2["status"] == "COMMITTED"        # writes resumed
        assert fleet_count(fe) == base + 2
        # exactly the elected primary holds the role in the routable fleet
        roles = [c for c in fe._alive()
                 if fe.workers[c].coord.role == "primary"]
        assert roles == [fe.membership.primary]


def test_midwave_crash_commits_exactly_once(chaos_db):
    """``primary.crash.midwave``: the wave committed, the primary died
    before storing a single result.  The retransmit to the promoted
    primary must resolve by rid to the ORIGINAL commit — once."""
    with mk_fleet(chaos_db, write_batch=1) as fe:
        a_gid, films = unlinked_films(fe.db)
        base = fleet_count(fe)
        fe.db.faults = FaultInjector(7).inject(
            "primary.crash.midwave", times=(0,))

        r = do_write(fe, [CreateEdge(films[0], a_gid, "film.actor")])
        assert fe.db.faults.fired, "the crash schedule never fired"
        assert r["status"] == "COMMITTED"         # original result, via rid
        assert fe.stats["failovers"] == 1
        assert fe.membership.epoch == 2
        assert not fe.workers[0].alive            # it really crashed
        assert fleet_count(fe) == base + 1        # once — never twice


def test_deposed_zombie_is_fenced_and_client_gets_retry_hint(chaos_db):
    """A primary partitioned from the CM keeps running with stale role
    state.  Its staged wave must be refused at the commit-time fence
    (store untouched), and the stranded client write resolves to
    ``ABORTED_FAILOVER`` with a retry hint — the retry then commits on
    the new primary."""
    with mk_fleet(chaos_db) as fe:                # default batch: wave open
        a_gid, films = unlinked_films(fe.db)
        base = fleet_count(fe)
        pub = fe.submit_write([CreateEdge(films[0], a_gid, "film.actor")])
        assert fe.write_result(pub) is None       # staged, wave still open

        # the CM declares worker 0 gone; worker 0 itself never hears it
        fe._handle_events(fe.membership.evict(0, reason="partition"))
        assert fe.membership.primary == 1 and fe.membership.epoch == 2

        zombie = fe.workers[0].coord
        assert zombie.role == "primary"           # missed its demote frame
        n = zombie.server.flush_writes()          # tries to commit anyway
        assert n == 1
        assert zombie.server.stats["write_fenced"] == 1
        assert fleet_count(fe) == base            # store untouched

        r = fe.write_result(pub)                  # resolved at failover
        assert r["status"] == "ABORTED_FAILOVER"
        assert r["retry_after_ms"] > 0
        r2 = do_write(fe, [CreateEdge(films[0], a_gid, "film.actor")])
        assert r2["status"] == "COMMITTED"
        assert fleet_count(fe) == base + 1


def test_lease_expiry_suspects_then_evicts_on_fake_clock(chaos_db):
    """``membership.heartbeat.drop`` starves worker 0's renewals; the
    fake clock walks its lease through alive -> suspect -> evicted and
    the election completes without a single real-time sleep."""
    t = {"now": 0.0}
    with mk_fleet(chaos_db, write_batch=1, lease_s=2.0,
                  membership_clock=lambda: t["now"]) as fe:
        a_gid, films = unlinked_films(fe.db)
        # renewals visit admitted members in cid order: worker 0 is
        # visits 0, 3, 6 across three pumps of a 3-worker fleet
        fe.db.faults = FaultInjector(3).inject(
            "membership.heartbeat.drop", action="race", times=(0, 3, 6))

        fe.pump()                                 # renewal lost, not late
        assert fe.membership.view()["leases"][0]["state"] == "alive"
        t["now"] = 2.5
        fe.pump()                                 # lease expired -> suspect
        assert fe.membership.view()["leases"][0]["state"] == "suspect"
        assert 0 not in fe._alive()               # no fresh traffic
        assert fe.membership.primary == 0         # not yet deposed
        t["now"] = 4.6
        fe.pump()                                 # grace expired -> evict
        view = fe.membership.view()
        assert view["leases"][0]["state"] == "evicted"
        assert view["primary"] == 1 and view["epoch"] == 2
        assert fe.stats["failovers"] == 1

        r = do_write(fe, [CreateEdge(films[0], a_gid, "film.actor")])
        assert r["status"] == "COMMITTED"


def test_forced_primary_expiry_and_stale_epoch_fence(chaos_db):
    """``membership.lease.expire`` force-expires the primary straight
    through suspect: one tick completes the whole failover.  A frame
    stamped with the old epoch then bounces ``STALE_EPOCH``."""
    t = {"now": 0.0}
    with mk_fleet(chaos_db, write_batch=1,
                  membership_clock=lambda: t["now"]) as fe:
        a_gid, films = unlinked_films(fe.db)
        fe.db.faults = FaultInjector(5).inject(
            "membership.lease.expire", action="race", times=(0,))

        fe.pump()                                 # one tick: evict + elect
        view = fe.membership.view()
        assert view["leases"][0]["state"] == "evicted"
        assert view["primary"] == 1 and view["epoch"] == 2
        assert fe.stats["failovers"] == 1

        # fencing: the promoted coordinator bounces old-config frames
        resp = fe.workers[1].request(
            {"op": "stats", "rid": "stale-probe", "epoch": 1})
        assert resp["status"] == "STALE_EPOCH" and resp["epoch"] == 2
        # ... and the frontend's restamp-and-retry makes that invisible
        resp = fe._rpc(1, {"op": "stats"})
        assert resp["status"] == "OK" and resp["stats"]["role"] == "primary"

        r = do_write(fe, [CreateEdge(films[0], a_gid, "film.actor")])
        assert r["status"] == "COMMITTED"


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
    checks = [HealthCheck.too_slow]
else:                                     # keep the decorators importable
    def given(**kw):
        return lambda fn: fn

    def settings(**kw):
        return lambda fn: fn
    seeds = checks = None


@pytest.mark.skipif(not HAVE_HYPOTHESIS,
                    reason="any-schedule sweep needs hypothesis (CI has it)")
@settings(max_examples=8, deadline=None, suppress_health_check=checks)
@given(seed=seeds)
def test_any_schedule_failover_invariants(chaos_db, seed):
    """Any seeded mix of writes, mid-wave primary crashes, worker kills,
    and lost heartbeats upholds the trio: the store holds exactly the
    COMMITTED writes (durability + exactly once), the routable fleet has
    at most the elected primary in the primary role (no split-brain),
    and every submitted write terminated with a definite answer."""
    rng = np.random.default_rng(seed)
    # frozen membership clock: wall-clock time (slow jax dispatches on a
    # loaded CI host) must not add lease expiries the schedule didn't ask
    # for — the lease state machine itself is pinned by the fake-clock
    # deterministic tests above; this sweep owns the write invariants
    with mk_fleet(chaos_db, write_batch=1,
                  membership_clock=lambda: 0.0) as fe:
        inj = FaultInjector(int(seed))
        fe.db.faults = inj
        a_gid, films = unlinked_films(fe.db)
        base = fleet_count(fe)
        outcomes, fi = [], 0
        for _ in range(10):
            action = int(rng.integers(0, 4))
            if action == 0 and fi < len(films):
                outcomes.append(do_write(
                    fe, [CreateEdge(films[fi], a_gid, "film.actor")]))
                fi += 1
            elif (action == 1 and fi < len(films)
                    and len(fe._alive()) > 1):
                # crash the primary right after this wave commits
                inj.inject("primary.crash.midwave",
                           times=(inj.visits("primary.crash.midwave"),))
                outcomes.append(do_write(
                    fe, [CreateEdge(films[fi], a_gid, "film.actor")]))
                fi += 1
            elif action == 2 and len(fe._alive()) > 1:
                fe.kill_worker(int(rng.choice(fe._alive())))
            else:
                inj.inject("membership.heartbeat.drop", action="race",
                           times=(inj.visits("membership.heartbeat.drop"),))
                fe.pump()
        fe.flush()

        statuses = [r["status"] for r in outcomes]
        assert all(s in ("COMMITTED", "ABORTED", "ABORTED_FAILOVER")
                   for s in statuses), statuses
        committed = statuses.count("COMMITTED")
        # durability + exactly once: an under-count is a lost ack, an
        # over-count is a double-apply — both are failures
        assert fleet_count(fe) == base + committed
        # no split-brain among routable workers
        roles = [c for c in fe._alive()
                 if fe.workers[c].coord.role == "primary"]
        p = fe.membership.primary
        assert roles == ([p] if p in fe._alive() else [])
        # every configuration change is fenced by an epoch bump
        evicted = [c for c, m in fe.membership.members.items()
                   if m.state == "evicted"]
        assert fe.membership.epoch == 1 + len(evicted)
