"""Management plane: telemetry, congestion control, closed-loop enforcement.

Everything here runs on simulated clocks — no jax, no sleeping — except the
one ServeEngine integration check at the bottom.
"""
import math

import numpy as np
import pytest

from repro.control import (
    Aimd, Dctcp, RateController, SharedBottleneckSim, SimTenant, WaterFill,
    max_min_fair,
)
from repro.control.telemetry import (
    EngineTelemetry, SchedulerTelemetry, TenantObs,
)
from repro.core.engine import CoreEngine, TokenBucket
from repro.serve.multiplex import bursty_trace, fair_replay, jain_index
from repro.serve.scheduler import Request, TenantScheduler


class _Payload:
    dtype = np.uint8

    def __init__(self, n):
        self.shape = (int(n),)


# --- token bucket ------------------------------------------------------------


def test_wait_time_zero_rate_returns_inf():
    """Regression: a hard-blocked (rate=0) tenant used to ZeroDivisionError."""
    b = TokenBucket(rate=0.0, capacity=10.0)
    assert b.consume(10, now=0.0)
    assert b.wait_time(1, now=0.0) == math.inf
    assert not b.consume(1, now=1e9)


def test_bucket_burst_then_backfill():
    b = TokenBucket(rate=100.0, capacity=300.0)
    now = 0.0
    assert b.consume(300, now)            # full burst available immediately
    assert not b.consume(1, now)
    assert not b.consume(150, now + 1.0)  # only 100 refilled
    assert b.consume(150, now + 1.5)      # 150 after 1.5s
    assert b.wait_time(300, now + 1.5) == pytest.approx(3.0)


def test_bucket_set_rate_preserves_tokens():
    b = TokenBucket(rate=100.0, capacity=100.0)
    assert b.consume(80, now=0.0)          # 20 left
    b.set_rate(10.0, now=0.5)              # settles +50 at the old rate first
    assert b.tokens == pytest.approx(70.0)
    assert b.rate == 10.0
    # new rate prices the future, not the past
    assert b.wait_time(100, now=0.5) == pytest.approx(3.0)


def test_bucket_drain_is_partial_and_never_negative():
    b = TokenBucket(rate=10.0, capacity=50.0)
    assert b.drain(30, now=0.0) == pytest.approx(30.0)
    assert b.drain(100, now=0.0) == pytest.approx(20.0)   # only what's left
    assert b.drain(5, now=0.0) == 0.0
    assert b.tokens == pytest.approx(0.0)


# --- max-min fair allocator ---------------------------------------------------


def test_max_min_fair_textbook():
    # capacity 10, demands 2/4/10 -> 2/4/4 (the classic example)
    assert max_min_fair(10, {1: 2, 2: 4, 3: 10}) == \
        pytest.approx({1: 2.0, 2: 4.0, 3: 4.0})


def test_max_min_fair_weighted_and_greedy():
    alloc = max_min_fair(90, {1: math.inf, 2: math.inf, 3: 10},
                         weights={1: 2.0, 2: 1.0, 3: 1.0})
    # tenant 3 takes 10; the 80 residual splits 2:1
    assert alloc[3] == pytest.approx(10.0)
    assert alloc[1] == pytest.approx(2 * alloc[2])
    assert sum(alloc.values()) == pytest.approx(90.0)


def test_max_min_fair_work_conserving_and_bounded():
    alloc = max_min_fair(100, {1: 20, 2: 30})
    assert alloc == pytest.approx({1: 20.0, 2: 30.0})   # under-demand: no pad
    alloc = max_min_fair(100, {1: math.inf, 2: math.inf, 3: math.inf})
    assert sum(alloc.values()) == pytest.approx(100.0)
    assert max_min_fair(0.0, {1: 5}) == {1: 0.0}
    assert max_min_fair(10.0, {}) == {}


def _sort_based_fill(demands, weights, capacity):
    """Exact weighted water-fill, computed independently of
    ``max_min_fair``: sort tenants by demand per unit weight, satisfy the
    longest prefix whose fill fits, and split what is left by weight at
    one common level. Demand or weight <= 0 gets 0; ``inf`` is greedy."""
    d = np.asarray(demands, dtype=np.float64)
    w = np.asarray(weights, dtype=np.float64)
    active = (d > 0) & (w > 0)
    w = np.where(active, w, 0.0)
    ratio = np.where(active, d / np.where(active, w, 1.0), np.inf)
    order = np.argsort(ratio, kind="stable")
    rs, ws = ratio[order], w[order]
    ds = np.where(active, d, 0.0)[order]
    finite = np.isfinite(rs)
    sat_demand = np.cumsum(np.where(finite, ds, 0.0))
    cum_w = np.cumsum(ws)
    total_w = cum_w[-1]
    # capacity needed to satisfy sorted prefix [0, i] outright, with
    # everyone after it held at level ratio[i]; non-decreasing in i
    fill_at = sat_demand + np.where(finite, rs, 0.0) * (total_w - cum_w)
    sat = finite & (fill_at <= capacity * (1 + 1e-12) + 1e-12)
    k = int(sat.sum())
    used_d = sat_demand[k - 1] if k else 0.0
    rest_w = total_w - (cum_w[k - 1] if k else 0.0)
    level = max((capacity - used_d) / rest_w, 0.0) if rest_w > 0 else 0.0
    out = np.empty_like(ds)
    out[order] = np.where(sat, ds, ws * level)
    return out


@pytest.mark.parametrize("weighting", ["equal", "mixed"])
@pytest.mark.parametrize("n", [1, 8, 100, 1000, 10000])
def test_max_min_fair_matches_sort_based_fill(n, weighting):
    """The progressive fill equals an exact sort-based fill on a seeded
    mix of backlogged (``inf``), satisfied and idle demands, never
    over-fills, and fills capacity whenever a tenant is backlogged."""
    cap = 1e6
    rng = np.random.default_rng(n)
    weights = (np.ones(n) if weighting == "equal"
               else rng.choice([1.0, 2.0, 4.0], size=n))
    demands = rng.uniform(0.2, 2.0, size=n) * 1.25 * (cap / n)
    kind = rng.random(n)
    demands[kind < 0.1] = math.inf
    demands[(kind >= 0.1) & (kind < 0.15)] = 0.0
    alloc = max_min_fair(cap, dict(enumerate(demands)),
                         dict(enumerate(weights)))
    got = np.array([alloc[t] for t in range(n)])
    want = _sort_based_fill(demands, weights, cap)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-9 * cap)
    assert got.sum() <= cap * (1 + 1e-12)
    if np.isinf(demands).any():
        assert got.sum() == pytest.approx(cap, rel=1e-9)


# --- dispatch enforcement -----------------------------------------------------


def test_dispatch_consumes_buckets_and_meters_shortfall():
    eng = CoreEngine(enforcement="account")
    eng.set_tenant_rate(1, bytes_per_s=100.0, burst=100.0)
    eng.set_tenant_rate(2, bytes_per_s=1000.0, burst=1000.0)
    for k in range(5):
        now = float(k)
        eng.dispatch("shm_move", _Payload(200), ("pod",), tenant_id=1, now=now)
        eng.dispatch("shm_move", _Payload(200), ("pod",), tenant_id=2, now=now)
    # tenant 1 offered 1000B at 100B/s: ~half deferred; tenant 2 untouched
    assert eng.total_bytes(1) == 1000
    assert eng.deferred_bytes(1) >= 400
    assert eng.deferred_bytes(2) == 0
    assert any(t == 1 for t, _, _ in eng.throttle_log)
    assert not any(t == 2 for t, _, _ in eng.throttle_log)


def test_dispatch_enforcement_off_by_default():
    eng = CoreEngine()
    eng.set_tenant_rate(1, bytes_per_s=1.0, burst=1.0)
    for _ in range(10):
        eng.dispatch("shm_move", _Payload(1000), ("pod",), tenant_id=1,
                     now=0.0)
    assert eng.deferred_bytes(1) == 0      # advisory buckets: seed behaviour


def test_engine_admit_ledger_tracks_admitted_and_wait():
    """CoreEngine-side admission ledger: in-rate ops/bytes per tenant plus
    the cumulative shaping delay enforcement charged."""
    eng = CoreEngine(enforcement="account")
    eng.set_tenant_rate(1, bytes_per_s=100.0, burst=100.0)
    eng.buckets[1].updated = 0.0
    eng.dispatch("shm_move", _Payload(60), ("pod",), tenant_id=1, now=0.0)
    eng.dispatch("shm_move", _Payload(60), ("pod",), tenant_id=1, now=0.0)
    eng.dispatch("shm_move", _Payload(10), ("pod",), tenant_id=2, now=0.0)
    snap = eng.admit_snapshot()
    ops1, bytes1, wait1 = snap[1]
    assert ops1 == 1                       # first op fully in-rate
    assert bytes1 == 100                   # 60 + the 40 the bucket covered
    assert wait1 == pytest.approx(20 / 100.0)   # shortfall / rate
    assert snap[2] == (1, 10, 0.0)         # uncapped tenant: no wait
    eng.reset_ledger()
    assert eng.admit_snapshot() == {}


def test_update_tenant_rate_keeps_balance():
    eng = CoreEngine(enforcement="account")
    eng.set_tenant_rate(1, 100.0, burst=100.0)
    eng.buckets[1].updated = 0.0
    eng.dispatch("shm_move", _Payload(70), ("pod",), tenant_id=1, now=0.0)
    eng.update_tenant_rate(1, 10.0, now=0.0)
    assert eng.buckets[1].tokens == pytest.approx(30.0)
    assert eng.buckets[1].rate == 10.0


# --- telemetry ----------------------------------------------------------------


def test_engine_telemetry_rates_and_counters():
    eng = CoreEngine(enforcement="account")
    tel = EngineTelemetry(eng, alpha=1.0, axes_filter=("pod",))
    tel.update(now=0.0)                                   # baseline
    eng.dispatch("shm_move", _Payload(500), ("pod",), tenant_id=3, now=0.5)
    obs = tel.update(now=1.0)
    assert obs[3].rate == pytest.approx(500.0)
    assert not obs[3].backlogged
    c = tel.counters()
    assert c['nk_offered_bytes_total{tenant="3",axes="pod"}'] == 500
    assert 'nk_served_bytes_per_s{tenant="3"}' in tel.export_prometheus()


def test_engine_telemetry_axes_filter_excludes_other_traffic():
    eng = CoreEngine(enforcement="account")
    tel = EngineTelemetry(eng, alpha=1.0, axes_filter=("pod",))
    tel.update(now=0.0)
    eng.dispatch("shm_move", _Payload(500), ("model",), tenant_id=3, now=0.5)
    obs = tel.update(now=1.0)
    assert obs.get(3, TenantObs()).offered == 0.0


def test_telemetry_deferred_marks_backlogged():
    eng = CoreEngine(enforcement="account")
    eng.set_tenant_rate(7, 100.0, burst=100.0)
    eng.buckets[7].updated = 0.0
    tel = EngineTelemetry(eng, alpha=1.0)
    tel.update(now=0.0)
    eng.dispatch("shm_move", _Payload(500), ("pod",), tenant_id=7, now=1.0)
    obs = tel.update(now=1.0 + 1e-3)
    assert obs[7].backlogged
    assert obs[7].rate < obs[7].offered


@pytest.mark.parametrize("reset", ["decreased", "vanished"])
def test_engine_telemetry_rebaselines_on_counter_reset(reset):
    """A CoreEngine counter that falls or disappears between samples (a
    migration folded the tenant's ledger out) reads as a rebaseline: no
    negative rate, and the EWMA restarts from the next interval."""
    eng = CoreEngine(enforcement="account")
    tel = EngineTelemetry(eng, alpha=0.5)
    tel.update(now=0.0)
    eng.dispatch("shm_move", _Payload(500), ("pod",), tenant_id=3, now=0.5)
    assert tel.update(now=1.0)[3].rate == pytest.approx(500.0)
    eng.export_tenant(3, now=1.0)                 # ledger folds out
    if reset == "decreased":
        eng.dispatch("shm_move", _Payload(100), ("pod",), tenant_id=3,
                     now=1.5)
    obs = tel.update(now=2.0)
    if reset == "decreased":
        assert obs[3] == TenantObs()
    else:
        assert 3 not in obs
        assert 3 not in tel.tracked_tenants()
    eng.dispatch("shm_move", _Payload(200), ("pod",), tenant_id=3, now=2.5)
    obs = tel.update(now=3.0)
    # a fresh EWMA: the 500 B/s before the reset does not leak in
    assert obs[3].rate == pytest.approx(200.0)
    assert obs[3].offered == pytest.approx(200.0)


# --- congestion-control algorithms -------------------------------------------


def _obs(rate, deferred=0.0, queue=0.0):
    return TenantObs(rate=rate, offered=rate + deferred, deferred=deferred,
                     queue=queue)


def test_aimd_backs_off_under_congestion_and_recovers():
    algo = Aimd(increase=10.0, decrease=0.5, min_rate=1.0)
    congested = {1: _obs(600.0), 2: _obs(600.0)}      # offered 1200 > 1000
    r1 = algo.allocate(congested, capacity=1000.0)
    r2 = algo.allocate(congested, capacity=1000.0)
    assert r2[1] == pytest.approx(r1[1] * 0.5)
    calm = {1: _obs(100.0), 2: _obs(100.0)}
    r3 = algo.allocate(calm, capacity=1000.0)
    assert r3[1] == pytest.approx(r2[1] + 10.0)


def test_dctcp_backoff_scales_with_marking_fraction():
    heavy, light = Dctcp(increase=5.0, g=1.0), Dctcp(increase=5.0, g=1.0)
    start = {1: _obs(500.0)}
    h0 = heavy.allocate(start, 1000.0)[1]
    # 50% of traffic deferred vs 5%: proportionally larger cut
    h1 = heavy.allocate({1: _obs(250.0, deferred=250.0)}, 1000.0)[1]
    l1 = light.allocate({1: _obs(475.0, deferred=25.0)}, 1000.0)[1]
    assert h1 == pytest.approx(h0 * (1 - 0.5 / 2))
    assert l1 == pytest.approx(h0 * (1 - 0.05 / 2))
    assert h1 < l1


def test_waterfill_satisfied_get_headroom_backlogged_split_residual():
    algo = WaterFill(headroom=1.2)
    obs = {1: _obs(100.0), 2: _obs(400.0, deferred=50.0),
           3: _obs(400.0, deferred=50.0)}
    alloc = algo.allocate(obs, capacity=1000.0)
    assert alloc[1] == pytest.approx(120.0)           # demand * headroom
    assert alloc[2] == pytest.approx(440.0)           # (1000-120)/2
    assert alloc[3] == pytest.approx(440.0)


# --- closed loop --------------------------------------------------------------


def test_controller_converges_to_max_min_fair():
    tenants = [SimTenant(1, 200.0), SimTenant(2, 900.0),
               SimTenant(3, 2000.0)]
    sim = SharedBottleneckSim(tenants, capacity=1000.0, dt=0.05)
    res = sim.run(10.0)
    ref = sim.fair_reference()
    assert ref == pytest.approx({1: 200.0, 2: 400.0, 3: 400.0})
    for t, want in ref.items():
        assert res.served_rate(t) == pytest.approx(want, rel=0.10)


def test_controller_distributed_engines_share_one_bottleneck():
    """Two engines, same fabric: per-tenant rate sums respect the global
    allocation and the split follows where the traffic is."""
    tenants = [SimTenant(1, 2000.0, engine_split=(0.75, 0.25)),
               SimTenant(2, 2000.0, engine_split=(0.25, 0.75))]
    sim = SharedBottleneckSim(tenants, capacity=1000.0, n_engines=2, dt=0.05)
    res = sim.run(10.0)
    for t in (1, 2):
        assert res.served_rate(t) == pytest.approx(500.0, rel=0.10)
    b0, b1 = sim.engines[0].buckets, sim.engines[1].buckets
    assert b0[1].rate > b1[1].rate        # tenant 1 mostly on engine 0
    assert b1[2].rate > b0[2].rate
    assert b0[1].rate + b1[1].rate == pytest.approx(500.0, rel=0.15)


def test_controller_weighted_shares():
    tenants = [SimTenant(1, 5000.0, weight=3.0),
               SimTenant(2, 5000.0, weight=1.0)]
    sim = SharedBottleneckSim(tenants, capacity=1000.0, dt=0.05)
    res = sim.run(10.0)
    assert res.served_rate(1) / res.served_rate(2) == pytest.approx(3.0,
                                                                    rel=0.15)


def test_controller_work_conserving_backfill():
    """When a tenant goes idle its share is re-absorbed; when it returns it
    gets its fair share back."""
    def on_off(t):
        return 900.0 if t < 5.0 or t >= 10.0 else 0.0
    tenants = [SimTenant(1, on_off), SimTenant(2, 2000.0)]
    sim = SharedBottleneckSim(tenants, capacity=1000.0, dt=0.05)
    sim.run(5.0)
    mid = sim.run(5.0)        # tenant 1 idle: tenant 2 absorbs the capacity
    assert mid.served_rate(2, 0.4, 1.0) == pytest.approx(1000.0, rel=0.10)
    back = sim.run(5.0)       # tenant 1 returns: back to 500/500
    assert back.served_rate(1, 0.5, 1.0) == pytest.approx(500.0, rel=0.15)
    assert back.served_rate(2, 0.5, 1.0) == pytest.approx(500.0, rel=0.15)


def test_controller_prometheus_export():
    tenants = [SimTenant(1, 500.0)]
    sim = SharedBottleneckSim(tenants, capacity=1000.0)
    sim.run(2.0)
    text = sim.controller.export_prometheus()
    assert "controller_ticks_total" in text
    assert 'nk_allocated_rate{tenant="1"}' in text


@pytest.mark.parametrize("n", [1000, 10000])
def test_controller_tick_matches_max_min_fair_at_scale(n):
    """Telemetry -> bids -> fill -> push over a whole tenant population:
    three ticks of seeded served counts, ~10% of tenants queued. The
    allocations are ``max_min_fair`` of the bids WaterFill forms
    (queued: ``inf``; otherwise the served rate x headroom), and every
    scheduler bucket carries its tenant's allocation."""
    cap = 1e6
    rng = np.random.default_rng(n)
    weights = rng.choice([1.0, 2.0, 4.0], size=n)
    steps = np.maximum(np.round(rng.uniform(0.2, 2.0, size=n) * (cap / n)),
                       1.0).astype(np.int64)
    queued = rng.random(n) < 0.1
    sched = TenantScheduler(policy="wfq", charge_prompt=True)
    ctrl = RateController(cap, weights=dict(enumerate(weights.tolist())),
                          alpha=0.5)
    ctrl.attach_scheduler(sched)
    for t in range(n):
        sched.add_tenant(t, weight=float(weights[t]))
        if queued[t]:
            sched.submit(Request(t, [1], 1, req_id=t))
    for tick in range(3):
        for t in range(n):
            sched.served_tokens[t] = int(steps[t]) * (tick + 1)
        alloc = ctrl.tick(float(tick))
    bids = {t: (math.inf if queued[t] else float(steps[t]) * 1.25)
            for t in range(n)}
    want = max_min_fair(cap, bids, dict(enumerate(weights.tolist())))
    assert set(alloc) == set(want)
    for t in range(n):
        assert abs(alloc[t] - want[t]) <= 1e-6 * cap
        assert sched.buckets[t].rate == alloc[t]
    assert sum(alloc.values()) == pytest.approx(cap, rel=1e-9)


def test_scheduler_telemetry_eviction():
    sched = TenantScheduler()
    tel = SchedulerTelemetry(sched, alpha=0.5)
    for t in (1, 2):
        sched.add_tenant(t)
        sched.served_tokens[t] = 10
    tel.update(now=0.0)
    sched.served_tokens[1] = 30
    sched.served_tokens[2] = 40
    tel.update(now=1.0)
    assert tel.tracked_tenants() >= {1, 2}
    sched.drop_tenant(1)
    tel.evict_tenant(1)
    assert 1 not in tel.tracked_tenants()
    assert 2 in tel.tracked_tenants()
    # the survivor's EWMA is untouched by the eviction
    obs = tel.update(now=2.0)
    assert 1 not in obs and 2 in obs


def test_controller_evict_tenant():
    sched = TenantScheduler()
    ctrl = RateController(1000.0, alpha=0.5)
    ctrl.attach_scheduler(sched)
    for t in (1, 2):
        sched.add_tenant(t)
        sched.served_tokens[t] = 5
    ctrl.tick(0.0)
    sched.served_tokens[1] = 25
    sched.served_tokens[2] = 25
    ctrl.tick(1.0)
    assert 1 in ctrl.allocations
    sched.drop_tenant(1)
    ctrl.evict_tenant(1)
    tel = ctrl._schedulers[0][1]
    assert 1 not in tel.tracked_tenants()
    assert 1 not in ctrl.allocations
    # a tenant the scheduler still holds is NOT evicted (migration source
    # that only moved one plane keeps live telemetry)
    ctrl.evict_tenant(2)
    assert 2 in tel.tracked_tenants()


def test_migration_finalize_evicts_source_telemetry():
    from test_placement import make_fake_cluster

    cluster = make_fake_cluster(2, controller=RateController(
        512.0, alpha=0.6))
    for t in range(2):
        cluster.add_tenant(t)
        for r in range(3):
            cluster.submit(Request(t, [1, 2], 4, req_id=10 * t + r,
                                   arrival=0.0))
    for i in range(8):
        cluster.step(now=0.1 * (i + 1))
    src = cluster.placement[0]
    tel_by_sched = {id(s): tel
                    for s, tel in cluster.controller._schedulers}
    src_tel = tel_by_sched[id(cluster.engines[src].scheduler)]
    assert 0 in src_tel.tracked_tenants()
    cluster.migrate(0, 1 - src, now=1.0)
    for i in range(12):
        cluster.step(now=1.0 + 0.1 * (i + 1))
    assert cluster.placement[0] == 1 - src
    assert 0 not in src_tel.tracked_tenants()      # the leak, plugged
    dst_tel = tel_by_sched[id(cluster.engines[1 - src].scheduler)]
    assert 0 in dst_tel.tracked_tenants()


# --- scheduler-side fairness --------------------------------------------------


def _drain_synthetic(sched, steps, tokens_per_req=10, dt=0.01):
    """Serve loop stand-in: admit one request per step, account its cost."""
    served = {t: 0 for t in sched.queues}
    now = 0.0
    for _ in range(steps):
        now += dt
        req = sched.next_request(now)
        if req is None:
            continue
        sched.account(req.tenant_id, tokens_per_req)
        served[req.tenant_id] += tokens_per_req
    return served


def test_wfq_share_convergence_unequal_weights():
    sched = TenantScheduler(policy="wfq")
    sched.add_tenant(1, weight=3.0)
    sched.add_tenant(2, weight=1.0)
    for i in range(400):
        sched.submit(Request(tenant_id=1 + i % 2, prompt=[1],
                             max_new_tokens=10))
    served = _drain_synthetic(sched, steps=200)
    assert served[1] / served[2] == pytest.approx(3.0, rel=0.10)


def test_scheduler_set_rate_midrun_takes_effect_and_keeps_balance():
    sched = TenantScheduler(policy="wfq")
    sched.add_tenant(1, rate_tokens_per_s=1000.0, burst=1000.0)
    sched.buckets[1].updated = 0.0
    sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=400))
    sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=400))
    assert sched.next_request(now=0.0) is not None     # 600 tokens left
    sched.set_rate(1, 1.0, now=0.0)                    # throttle hard...
    assert sched.buckets[1].tokens == pytest.approx(600.0)   # ...balance kept
    assert sched.next_request(now=0.0) is not None     # balance still covers
    sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=400))
    assert sched.next_request(now=0.0) is None         # 200 left: blocked
    sched.set_rate(1, None)                            # lift the cap
    assert sched.next_request(now=0.0) is not None


def test_set_rate_on_unknown_tenant_creates_no_ghost_queue():
    """Regression: a controller probing every enforcement point used to
    register full queue state for tenants that never submitted here — ghost
    tenants WFQ/RR scanned forever, each holding a stale rate entry."""
    sched = TenantScheduler()
    sched.add_tenant(1)
    sched.set_rate(5, 100.0, now=0.0)          # tenant 5 never submitted
    assert 5 not in sched.queues
    assert 5 not in sched._rr_order
    assert sched.pending() == 0
    assert 5 in sched.buckets                  # the rate itself does apply...
    sched.submit(Request(tenant_id=5, prompt=[1], max_new_tokens=40))
    assert 5 in sched.queues                   # ...once the tenant shows up
    assert sched.next_request(now=0.0) is not None   # burst covers 40
    sched.submit(Request(tenant_id=5, prompt=[1], max_new_tokens=400))
    assert sched.next_request(now=0.0) is None       # and then rate-bound


def test_drop_tenant_clears_stale_rate_entry():
    """Regression: zero-queue tenants kept their last pushed rate forever;
    a tenant returning after drop_tenant starts uncapped, not throttled."""
    sched = TenantScheduler()
    sched.add_tenant(1)
    sched.set_rate(1, 1e-6, now=0.0)           # throttled hard, then departs
    assert sched.pending(1) == 0
    sched.drop_tenant(1)
    assert 1 not in sched.buckets and 1 not in sched.queues
    assert 1 not in sched._rr_order
    sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=400))
    assert sched.next_request(now=1.0) is not None   # no stale 1e-6 cap


def test_scheduler_admission_ledger():
    """admit/defer/latency counters the replay harness reads."""
    sched = TenantScheduler()
    sched.add_tenant(1, rate_tokens_per_s=10.0, burst=10.0)
    sched.buckets[1].updated = 0.0
    sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=10,
                         arrival=0.0))    # t=0 arrival must count (regression)
    sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=10,
                         arrival=0.0))
    assert sched.next_request(now=1.0) is not None   # burst covers one
    assert sched.next_request(now=1.0) is None       # second: deferred
    led = sched.ledger()[1]
    assert led["admitted_requests"] == 1
    assert led["deferred_polls"] >= 1
    assert led["mean_admit_wait_s"] == pytest.approx(1.0)


@pytest.mark.parametrize("path", ["export", "checkpoint"])
def test_scheduler_tenant_state_roundtrip(path):
    """A partly drained bucket, the WFQ weight and the queue cross to
    another scheduler unchanged: by migration (export -> import) and by
    checkpoint (snapshot -> restore)."""
    now = 1.0
    src, dst = TenantScheduler(), TenantScheduler()
    src.add_tenant(1, weight=2.0, rate_tokens_per_s=100.0, burst=50.0)
    src.submit(Request(1, [1, 2], 3, req_id=9))
    assert src.buckets[1].consume(20.0, now=now)
    want = src.buckets[1].snapshot(now=now)
    assert want == pytest.approx({"rate": 100.0, "capacity": 50.0,
                                  "tokens": 30.0, "updated": now})
    if path == "export":
        dst.import_tenant(1, src.export_tenant(1, now=now), now=now)
        assert 1 not in src.buckets and 1 not in src.queues
    else:
        dst.restore_tenant(1, src.snapshot_tenant(1, now=now), now=now)
        assert src.buckets[1].snapshot(now=now) == want  # non-destructive
    assert dst.buckets[1].snapshot(now=now) == want
    assert dst.weights[1] == 2.0
    assert [r.req_id for r in dst.queues[1]] == [9]
    # refill resumes at the carried rate from the carried level
    assert dst.buckets[1].wait_time(50.0, now=now) == pytest.approx(0.2)


def test_delta_push_cuts_chatter_in_fluid_sim():
    """Same closed loop, push_mode=delta: far fewer set_rate calls on a
    stable workload, same converged allocation."""
    tenants = [SimTenant(1, 200.0), SimTenant(2, 900.0), SimTenant(3, 2000.0)]
    full = SharedBottleneckSim(tenants, capacity=1000.0, dt=0.05,
                               push_mode="full")
    delta = SharedBottleneckSim(
        [SimTenant(1, 200.0), SimTenant(2, 900.0), SimTenant(3, 2000.0)],
        capacity=1000.0, dt=0.05, push_mode="delta")
    rf, rd = full.run(10.0), delta.run(10.0)
    assert delta.controller.push_calls <= 0.25 * full.controller.push_calls
    assert delta.controller.push_skipped > 0
    for t, want in full.fair_reference().items():
        assert rd.served_rate(t) == pytest.approx(want, rel=0.12)
    c = delta.controller.counters()
    assert c["controller_push_calls_total"] == delta.controller.push_calls
    assert c["controller_push_skipped_total"] > 0


def test_delta_push_refresh_recovers_external_reset():
    """Soft-state refresh: if an enforcement point is reset behind the
    controller's back (drop_tenant), delta mode re-pushes within
    refresh_every ticks instead of skipping forever."""
    sched = TenantScheduler()
    sched.add_tenant(1)
    ctrl = RateController(capacity=100.0, push_mode="delta",
                          refresh_every=5).attach_scheduler(sched)
    now = 0.0
    for _ in range(20):
        now += 0.05
        sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=5))
        req = sched.next_request(now)
        if req is not None:
            sched.account(1, 5)
        ctrl.tick(now)
    assert 1 in sched.buckets
    rate_before = sched.buckets[1].rate
    sched.drop_tenant(1)                       # external reset
    assert 1 not in sched.buckets
    for _ in range(2 * 5):                     # at most one refresh period...
        now += 0.05
        sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=5))
        req = sched.next_request(now)
        if req is not None:
            sched.account(1, 5)
        ctrl.tick(now)
    assert 1 in sched.buckets                  # ...and the cap is back
    assert sched.buckets[1].rate == pytest.approx(rate_before, rel=0.5)


def test_controller_drives_scheduler_buckets():
    """Serving-side loop: queue-backlogged tenants end up at equal token
    rates without any engine involved."""
    sched = TenantScheduler(policy="wfq")
    sched.add_tenant(1)
    sched.add_tenant(2)
    ctrl = RateController(capacity=100.0).attach_scheduler(sched)
    for i in range(100):
        sched.submit(Request(tenant_id=1 + i % 2, prompt=[1],
                             max_new_tokens=5))
    now = 0.0
    for _ in range(200):
        now += 0.05
        req = sched.next_request(now)
        if req is not None:
            sched.account(req.tenant_id, 5)
        ctrl.tick(now)
    assert set(ctrl.allocations) == {1, 2}
    assert ctrl.allocations[1] == pytest.approx(ctrl.allocations[2],
                                                rel=0.25)
    assert sched.buckets[1].rate == pytest.approx(ctrl.allocations[1])
    # pushed rates must not shrink bucket capacity below a request's cost
    # (requests admit whole: a tiny burst would head-of-line-block forever)
    assert sched.buckets[1].capacity >= 5


def test_controller_recovers_hard_blocked_scheduler_tenant():
    """A tenant starting at rate=0/burst=0 must become servable once the
    controller raises its rate (capacity grows to >= 1s of the new rate)."""
    sched = TenantScheduler()
    sched.add_tenant(1, rate_tokens_per_s=0.0, burst=0.0)
    ctrl = RateController(capacity=50.0).attach_scheduler(sched)
    for _ in range(10):
        sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=5))
    now, served = 0.0, 0
    for _ in range(100):
        now += 0.1
        req = sched.next_request(now)
        if req is not None:
            sched.account(1, 5)
            served += 1
        ctrl.tick(now)
    assert served == 10
    assert sched.buckets[1].capacity >= sched.buckets[1].rate


def test_controller_splits_allocation_across_schedulers():
    """Two serving hosts, one token bottleneck: per-tenant rates are split,
    not granted in full at each host (which would over-admit 2x)."""
    s1, s2 = TenantScheduler(), TenantScheduler()
    ctrl = RateController(capacity=100.0)
    ctrl.attach_scheduler(s1).attach_scheduler(s2)
    now = 0.0
    for k in range(40):
        now += 0.05
        for sched in (s1, s2):
            sched.submit(Request(tenant_id=1, prompt=[1], max_new_tokens=5))
            req = sched.next_request(now)
            if req is not None:
                sched.account(req.tenant_id, 5)
        ctrl.tick(now)
    total_rate = s1.buckets[1].rate + s2.buckets[1].rate
    assert total_rate == pytest.approx(ctrl.allocations[1], rel=1e-6)
    assert total_rate <= 100.0 * (1 + 1e-6)


# --- fair replay --------------------------------------------------------------


def test_fair_replay_work_conserving_and_fair():
    t = bursty_trace(6, seed=3)
    cap = float(t.loads.sum(axis=0).mean()) * 0.6      # force contention
    out = fair_replay(t, cap)
    assert out["jain_backlogged"] > 0.99    # contested capacity split evenly
    served_rates = out["served"].sum(axis=0)
    assert float(served_rates.max()) <= cap * (1 + 1e-6)
    # work conservation: when demand exceeds cap, serve exactly cap
    demand = t.loads.sum(axis=0)
    congested = demand > cap * 1.01
    assert congested.any()
    np.testing.assert_allclose(served_rates[congested], cap, rtol=1e-6)


def test_fair_replay_rate_caps_leave_capacity_to_others():
    t = bursty_trace(3, seed=0)
    cap = float(t.loads.sum(axis=0).max())             # ample capacity
    out = fair_replay(t, cap, rate_caps={0: 1.0})
    assert float(out["served"][0].max()) <= 1.0 + 1e-6
    # the capped tenant's unused share went to the others, not to waste
    others_served = out["served"][1:].sum()
    others_offered = t.loads[1:].sum()
    assert others_served == pytest.approx(others_offered, rel=1e-6)


def test_jain_index():
    assert jain_index([5, 5, 5, 5]) == pytest.approx(1.0)
    assert jain_index([1, 0, 0, 0]) == pytest.approx(0.25)
    assert jain_index([]) == 1.0


# --- ServeEngine integration --------------------------------------------------


def test_serve_engine_ticks_controller(mesh1, rcfg_small):
    from repro.configs import get_smoke_config
    from repro.serve import Request as SReq, ServeEngine

    class TickCounter:
        def __init__(self):
            self.ticks = []

        def tick(self, now=None):
            self.ticks.append(now)

    ctrl = TickCounter()
    eng = ServeEngine(get_smoke_config("llama3.2-3b"), rcfg_small, mesh1,
                      batch_slots=2, max_seq=32, controller=ctrl,
                      control_every=2)
    for i in range(3):
        eng.submit(SReq(tenant_id=i % 2, prompt=[1, 2], max_new_tokens=6,
                        req_id=i))
    eng.run_until_drained()
    # ticks follow step() calls (not just decode steps): a fully-throttled
    # engine with zero active slots must still reach the controller
    assert len(ctrl.ticks) == eng.steps // 2
    assert eng.steps >= eng.decode_steps
