"""End-to-end replay scenarios: the paper's fairness claims measured on a
real ServeEngine (jitted prefill/decode, WFQ admission, controller-enforced
token buckets), not on the fluid model.

The smoke test stays in tier-1 so every push exercises the harness; the
full scenarios are `slow` (CI runs them in a dedicated job, locally via
`pytest -m slow`).
"""
import numpy as np
import pytest

from repro.serve.multiplex import Trace
from repro.serve.replay import (
    TOKENS_PER_REQUEST, TraceReplayer, adversarial_baseline,
    make_replay_cluster, make_replay_engine, replay_scenario, scenario_spec,
)


def _report(trace, *, capacity, push_mode="full", weights=None,
            unit="requests"):
    eng = make_replay_engine(capacity=capacity, push_mode=push_mode,
                             weights=weights)
    rep = TraceReplayer(eng, capacity=capacity, weights=weights)
    return rep.run(trace, unit=unit)


def test_replay_smoke_reports_from_real_ledgers():
    """Tier-1: the harness drives a real engine and measures per-tenant
    rates, admission latency and fairness from scheduler ledgers."""
    trace, cap = scenario_spec("steady", n_tenants=2, intervals=6)
    rep = _report(trace, capacity=cap)
    assert rep.decode_steps > 0
    assert set(rep.per_tenant) == {0, 1}
    for r in rep.per_tenant.values():
        assert r.achieved_rate > 0
        assert r.admitted_requests > 0
        assert r.completed_requests > 0
        assert r.served_tokens == pytest.approx(
            r.achieved_rate * rep.duration_s)
    # contention means both tenants were bucket-deferred at some point
    assert sum(r.deferred_polls for r in rep.per_tenant.values()) > 0
    assert rep.jain() > 0.95
    # work conservation under contention: the bottleneck is busy
    assert rep.total_rate() > 0.8 * cap


def test_suspend_resume_serves_bit_identical():
    """Tier-1 tentpole guard: suspend() drops the KV-cache and slot
    buffers (bytes freed > 0); resume() lazily re-materializes them on
    the next admission; and serving after the cycle is bit-identical to
    the never-parked behavior (same generated tokens, same ledger
    arithmetic) — parking is a real memory saving with no serving cost."""
    from repro.serve.scheduler import Request

    eng = make_replay_engine(capacity=1e6, batch_slots=2)

    def serve(req_id):
        eng.submit(Request(tenant_id=0, prompt=[1, 2], max_new_tokens=4,
                           req_id=req_id, arrival=0.0))
        for k in range(12):
            eng.step(now=0.1 * (k + 1))
        return eng.completed[-1]

    before = serve(0)                      # the never-parked reference
    resident = eng.resident_bytes()
    assert resident > 0
    freed = eng.suspend()
    assert freed == resident
    assert eng.resident_bytes() == 0 and eng.caches is None
    assert eng.slots == []                 # slot buffers dropped too
    with pytest.raises(RuntimeError):      # a parked engine never steps
        eng.step(now=9.9)
    eng.resume()
    assert eng.caches is None              # lazy: nothing resident yet...
    after = serve(1)
    assert eng.resident_bytes() == resident    # ...until a request lands
    # bit-identical serving: same tokens, same billing as never-parked
    assert after.generated == before.generated
    assert eng.scheduler.served_tokens[0] == sum(
        len(r.prompt) + len(r.generated) for r in eng.completed)


def test_single_token_request_billing_matches_bucket_price():
    """Regression: max_new_tokens=1 used to occupy a decode slot anyway,
    generating (and billing) a 2nd token past the bucket's price."""
    from repro.serve.scheduler import Request

    eng = make_replay_engine(capacity=100.0, batch_slots=2)
    eng.submit(Request(tenant_id=0, prompt=[1, 2], max_new_tokens=1,
                       arrival=0.0))
    for k in range(4):
        eng.step(now=0.1 * (k + 1))
    assert len(eng.completed) == 1
    req = eng.completed[0]
    assert len(req.generated) == 1                # exactly what was asked
    # ledger bills prompt + the one prefill token = the bucket's price
    assert eng.scheduler.served_tokens[0] == len(req.prompt) + 1


@pytest.mark.slow
def test_replay_convergence_jain_and_max_min():
    """Fig. 21 end-to-end: contended steady state converges to max-min fair
    within 10%, Jain >= 0.95, measured from engine ledgers."""
    trace, cap = scenario_spec("steady", n_tenants=4, intervals=18)
    rep = _report(trace, capacity=cap)
    assert rep.jain() >= 0.95
    assert rep.max_min_deviation() < 0.10


@pytest.mark.slow
def test_replay_misbehaver_isolation():
    """Fig. 22 end-to-end: a 10x-overloading tenant degrades in-budget
    tenants' served rate by < 5% vs their hog-free baseline."""
    n, intervals = 4, 16
    hog_trace, cap = scenario_spec("adversarial", n_tenants=n,
                                   intervals=intervals)
    base_trace = adversarial_baseline(hog_trace)
    baseline = _report(base_trace, capacity=cap)
    shared = _report(hog_trace, capacity=cap)
    for t in range(n - 1):                        # the in-budget tenants
        degr = 1.0 - (shared.per_tenant[t].achieved_rate
                      / baseline.per_tenant[t].achieved_rate)
        assert degr < 0.05, f"tenant {t} degraded {degr:.1%}"
    # and the hog is contained, not starved: it gets the leftover capacity
    hog = shared.per_tenant[n - 1]
    assert hog.achieved_rate < 0.75 * cap
    assert hog.achieved_rate > 0.25 * cap
    # the hog pays the queueing price, not its neighbours
    in_budget_wait = max(shared.per_tenant[t].mean_admit_wait_s
                         for t in range(n - 1))
    assert hog.mean_admit_wait_s > 4 * max(in_budget_wait, 1e-3)
    # tail latency (histogram estimates, see repro.obs.hist): the
    # victims' p99 admit wait stays bounded — under a second even with
    # the hog offering 10x — their median stays at the no-contention
    # floor, and the hog's own p99 sits an order of magnitude above its
    # victims': the tail price lands on the tenant that caused it
    victim_p99 = max(shared.per_tenant[t].p99_admit_wait_s
                     for t in range(n - 1))
    assert 0.0 < victim_p99 < 1.0
    assert max(shared.per_tenant[t].p50_admit_wait_s
               for t in range(n - 1)) <= 0.01
    assert hog.p99_admit_wait_s > 10 * victim_p99


@pytest.mark.slow
def test_replay_work_conserving_backfill():
    """A tenant going idle mid-trace frees capacity that the backlogged
    tenant absorbs (measured on the engine, interval by interval)."""
    intervals = 18
    third = intervals // 3
    loads = np.zeros((2, intervals))
    loads[0, :] = 4.0
    loads[0, third:2 * third] = 0.0               # tenant 0 idle mid-run
    loads[1, :] = 12.0                            # always backlogged
    cap = 8.0 * TOKENS_PER_REQUEST                # 8 req/s of bottleneck
    eng = make_replay_engine(capacity=cap, control_every=4)
    rep = TraceReplayer(eng, capacity=cap)
    reports = [rep.run(Trace(loads=loads[:, lo:hi]))
               for lo, hi in ((0, third), (third, 2 * third),
                              (2 * third, intervals))]
    on1, off, on2 = ({t: r.per_tenant[t].achieved_rate for t in (0, 1)}
                     for r in reports)
    # ledger windowing regression: tenant 0 admits nothing while idle, so
    # its *windowed* admission stats must be 0, not phase-1 leakage
    assert reports[1].per_tenant[0].admitted_requests == 0
    assert reports[1].per_tenant[0].mean_admit_wait_s == 0.0
    # idle phase: the survivor absorbs (nearly) the whole bottleneck
    assert off[1] > 0.85 * cap
    assert off[1] > 1.25 * on1[1]
    # return phase: tenant 0 is served again at (near) its demand
    assert on2[0] > 0.8 * (4.0 * TOKENS_PER_REQUEST)


@pytest.mark.slow
def test_replay_migration_scenario_bounds_hold_across_move():
    """The multi-engine scenario: 3 engines, one controller, the 10x hog
    heats its engine and a mid-window rebalance migrates it live. Jain and
    in-budget evenness must hold across the migration window."""
    rep = replay_scenario("migration", n_tenants=4, intervals=16, engines=3)
    assert rep.engines == 3
    assert rep.migrations >= 1
    assert rep.placement is not None and rep.placement[3] != 0
    assert rep.jain() >= 0.95
    # in-budget tenants (equal demand) stay even despite hog + migration
    rates = [rep.per_tenant[t].achieved_rate for t in range(3)]
    assert max(rates) / min(rates) < 1.05
    # the migration scenario refuses to run without a cluster
    with pytest.raises(ValueError):
        replay_scenario("migration", n_tenants=4, intervals=4, engines=1)


@pytest.mark.slow
def test_replay_migrate_hog_mid_burst_conserves_ledger():
    """Satellite edge case: migrating the hog itself mid-burst — a huge
    unserved queue plus live in-flight slots — must conserve its
    served-token ledger exactly (no loss, no double-billing)."""
    trace, cap = scenario_spec("migration", n_tenants=4, intervals=14)
    cl = make_replay_cluster(capacity=cap, engines=3)
    recs = []

    def ev(cluster, now):
        recs.append(cluster.migrate(3, cluster.coolest_engine(), now=now))

    rep = TraceReplayer(cl, capacity=cap).run(trace, events=[(7, ev)])
    rec = recs[0]
    assert rec is not None
    assert rec.inflight_at_move > 0           # genuinely mid-burst
    assert rec.queued_moved > 0               # the backlog travelled
    assert rep.migrations == 1 and not cl.draining
    cl.assert_ledger_conservation(3)
    assert cl.tenant_served_tokens(3) == cl.tenant_billed_ground_truth(3)
    # neighbours stayed isolated across the move
    assert rep.jain() >= 0.95


@pytest.mark.slow
def test_replay_consolidation_scenario_parks_and_recovers():
    """The closed placement loop on real engines: busy -> idle -> busy.
    The autopilot packs the idle fleet, parks >= 1 engine (cores AND
    memory saved — parked engines suspend their KV-caches), and wakes
    the cluster when load returns — fairness intact and serving
    bit-identical after resume."""
    trace, cap = scenario_spec("consolidation", n_tenants=4, intervals=12)
    cl = make_replay_cluster(capacity=cap, engines=3,
                             autopilot="consolidate")
    rep = TraceReplayer(cl, capacity=cap).run(trace)
    assert rep.engines == 3
    assert rep.max_parked >= 1                    # idle window parked
    assert rep.cores_saved > 0
    # the memory-saved claim: bytes were freed while parked
    assert rep.max_parked_bytes > 0
    assert rep.mem_saved_bytes > 0
    assert rep.peak_resident_cache_bytes > rep.max_parked_bytes
    assert rep.autopilot_moves >= 1               # the loop found the pack
    assert rep.jain() >= 0.95
    # load returned: every tenant is placed and served
    assert all(r.achieved_rate > 0 for r in rep.per_tenant.values())
    # bit-identical serving across suspend/resume: every request in this
    # scenario has the same prompt, so a resumed engine whose re-init
    # cache changed anything would show up as a divergent generation
    seqs = {tuple(r.generated) for r in cl.completed}
    assert len(seqs) == 1
    with pytest.raises(ValueError):
        replay_scenario("consolidation", n_tenants=4, intervals=4,
                        engines=1)


@pytest.mark.slow
def test_replay_hotspot_autopilot_migrates_hog_both_planes():
    """The developing hog is auto-migrated by the closed loop — no
    operator event anywhere — with ledger conservation on the serve AND
    bytes planes and zero ping-pong under hysteresis."""
    from repro.core.nqe import CommOp
    from repro.serve.replay import make_replay_cluster

    n, intervals = 4, 14
    trace, cap = scenario_spec("hotspot", n_tenants=n, intervals=intervals)
    cl = make_replay_cluster(capacity=cap, engines=3,
                             autopilot="spread_hot", core_plane=True)
    pumped = {}

    def pump(cluster, now):
        for t, k in sorted(cluster.placement.items()):
            op = CommOp(verb="psum", axes=("pod",), tenant_id=t,
                        size_bytes=2048)
            cluster.core_engines[k].admit(op, now)
            cluster.core_engines[k].route(op)
            pumped[t] = pumped.get(t, 0) + 2048

    rep = TraceReplayer(cl, capacity=cap).run(
        trace, events=[(i, pump) for i in range(intervals)])
    hog = n - 1
    moved = [mv.tenant for _, mv in cl.autopilot.move_log]
    assert moved.count(hog) == 1                  # auto-migrated, once
    assert len(moved) == len(set(moved))          # nobody moved twice
    cl.autopilot.assert_no_ping_pong()
    assert rep.autopilot_moves == len(moved)
    for t in range(n):
        cl.assert_ledger_conservation(t)          # serve plane
        assert cl.tenant_core_bytes(t) == pumped[t]   # bytes plane
    assert rep.jain() >= 0.95


@pytest.mark.slow
def test_replay_stack_swap_scenario_swaps_both_planes_live():
    """The paper's hot-swap headline on real jitted engines: mid-burst,
    one serve engine's module is swapped for the alternate scheduler
    variant (reusing the retired stack's weights and compiled
    prefill/decode) and one CoreEngine flips native -> compressed
    transport — under traffic, with zero dropped or double-billed
    tokens on either plane and fairness intact."""
    from repro.serve.replay import stack_swap_events

    n, intervals = 4, 12
    trace, cap = scenario_spec("stack_swap", n_tenants=n,
                               intervals=intervals)
    cl = make_replay_cluster(capacity=cap, engines=3, core_plane=True)
    rep = TraceReplayer(cl, capacity=cap).run(
        trace, events=stack_swap_events(intervals))
    assert rep.swaps == 2
    assert {r.plane for r in cl.swap_log} == {"serve", "bytes"}
    serve_rec = next(r for r in cl.swap_log if r.plane == "serve")
    bytes_rec = next(r for r in cl.swap_log if r.plane == "bytes")
    # the serve swap flipped the scheduler policy on the swapped slot...
    assert cl.engines[serve_rec.engine].scheduler.policy == "rr"
    # ...and the bytes swap flipped the transport beneath the same fleet
    assert cl.core_engines[bytes_rec.engine].default_nsm == "compressed"
    assert serve_rec.old_stack != serve_rec.new_stack
    assert bytes_rec.old_stack != bytes_rec.new_stack
    # conservation, exactly, on both planes, for every tenant: the swap
    # dropped nothing and double-billed nothing
    for t in range(n):
        cl.assert_ledger_conservation(t)
        assert cl.tenant_served_tokens(t) == \
            cl.tenant_billed_ground_truth(t)
        assert cl.tenant_core_bytes(t) == intervals * 4096
    assert rep.jain() >= 0.95
    counters = cl.counters()
    assert counters['nk_swaps_total{plane="serve"}'] == 1.0
    assert counters['nk_swaps_total{plane="bytes"}'] == 1.0
    # replay_scenario wires the same thing end to end
    rep2 = replay_scenario("stack_swap", n_tenants=n, intervals=intervals)
    assert rep2.swaps == 2


@pytest.mark.slow
def test_replay_delta_push_is_quiet_on_stable_trace():
    """Delta-based push: on a steady trace the controller issues a small
    fraction of full-push set_rate calls — O(changed), not O(tenants)."""
    trace, cap = scenario_spec("steady", n_tenants=4, intervals=14)
    full = _report(trace, capacity=cap, push_mode="full")
    delta = _report(trace, capacity=cap, push_mode="delta")
    assert full.set_rate_calls > 0
    assert delta.set_rate_calls <= 0.25 * full.set_rate_calls
    # and enforcement quality did not regress
    assert delta.jain() >= 0.95
    assert delta.max_min_deviation() < 0.12
    # the skipped pushes are accounted, proving the gate actually ran
    assert delta.push_skipped > delta.set_rate_calls
