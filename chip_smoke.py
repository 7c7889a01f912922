"""Chip smoke run: the fabric's main path on one TPU chip, at full width.

Drives the served path through the entry points a user calls, at the
published width of internlm2-1.8b (24 layers, d_model 2048, 16 heads with
8 KV heads, d_ff 8192, vocab 92544) with bf16 weights drawn from ``--seed``:

  serve    one ServeEngine (8 slots, max_seq 2048) under a WFQ
           TenantScheduler and an attached RateController: 12 requests
           from 3 tenants, prompts of 16/128/512 tokens, 32 new tokens
           each. Checks completion, billing, and every generated token
           against a teacher-forced full-sequence forward.
  fabric   an EngineCluster of two engines sharing those weights and the
           compiled prefill/decode: one live migrate and one serve-plane
           stack swap (wfq -> rr) under traffic, then ledger conservation
           for every tenant and no dropped tokens.

``--four-chips`` runs only the NSM collective phase, on a (pod=2, data=2)
mesh of four chips: nk_psum(gradient=True) under all five routing
policies, and the ring all_gather / reduce_scatter, each against the
native collective.

Times and memory printed here are smoke figures, not benchmark metrics.
The script runs in one process and starts none. It exits non-zero when
JAX finds no TPU, and on any failed check; its last line of standard
output is ``{"ok": true, "device": {...}}`` and is printed only on success.

Run: python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODEL = "internlm2-1.8b"
SLOTS = 8
MAX_SEQ = 2048
PROMPT_LENS = (16, 128, 512)
NEW_TOKENS = 32
CAPACITY = 1e6            # tokens/s: the controller shapes, never stalls
# Served and reference logits are bf16, each rounded from a hidden state
# that 24 layers of bf16 arithmetic reached in a different order (cached
# decode against one full forward). Random weights make the logits flat
# (sd ~0.15, top ~0.64), so near-ties are common: on a TPU v5e at seed 0
# a served token sat at most 8 bf16 ulps of the top logit below the
# reference's argmax. 16 ulps leave twice that; a token drawn at random
# sits ~160 ulps below the top.
GAP_ULPS = 16
# share of generated tokens that must equal the reference argmax exactly
# (85-88% on a TPU v5e at seed 0; a wrong path matches almost none)
MIN_EQUAL = 0.75


class CompileClock:
    """Sums JAX's compile-phase durations (trace, lowering, backend)."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs


def check(cond, msg):
    if not cond:
        raise SystemExit(f"FAILED: {msg}")


def smoke_figures(label, t0, c0, clock):
    """Wall and compile seconds since (t0, c0), and the device's peak
    memory so far: set-up figures of this run, not benchmark metrics."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{label}] smoke figures, not benchmark metrics: wall "
          f"{time.perf_counter() - t0:.1f} s, of which compile "
          f"{clock.seconds - c0:.1f} s; peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')}")


def make_requests(rng, *, n, tenants, vocab, first_id=0):
    from repro.serve.scheduler import Request
    reqs = []
    for i in range(n):
        plen = int(rng.choice(PROMPT_LENS))
        reqs.append(Request(
            tenant_id=i % tenants,
            prompt=[int(t) for t in rng.integers(0, vocab, plen)],
            max_new_tokens=NEW_TOKENS, req_id=first_id + i,
            arrival=time.monotonic()))
    return reqs


def check_completed(reqs, completed, billed):
    """Every request finished with the tokens it asked for, and each
    tenant was billed prompt + generated tokens, exactly."""
    done = {r.req_id for r in completed}
    check(len(completed) == len(reqs) and done == {r.req_id for r in reqs},
          f"{len(completed)}/{len(reqs)} requests completed")
    for r in reqs:
        check(len(r.generated) == r.max_new_tokens,
              f"request {r.req_id}: {len(r.generated)} of "
              f"{r.max_new_tokens} tokens")
    want = {}
    for r in reqs:
        want[r.tenant_id] = want.get(r.tenant_id, 0) + \
            len(r.prompt) + len(r.generated)
    for t, n in sorted(want.items()):
        check(billed(t) == n, f"tenant {t} billed {billed(t)}, served {n}")
    return want


def make_reference(cfg, rcfg, mesh):
    """The plain reference: the full-sequence forward (no KV cache) over
    prompt + generated. At each position that predicted a generated token
    it returns the argmax, the top two logits and the logit of the token
    that was generated. It is the prefill path at every position:
    ``forward_prefill`` keeps only the last position's logits."""
    import jax
    import jax.numpy as jnp
    from repro.distribution.sharding import ShardingCtx
    from repro.models.model import forward_train

    shd = ShardingCtx(mesh)

    @jax.jit
    def reference(params, tokens, generated):
        logits, _ = forward_train(params, {"tokens": tokens}, cfg, shd, rcfg)
        s = tokens.shape[1]
        # position p predicts token p + 1
        lf = logits[0, s - NEW_TOKENS - 1:s - 1].astype(jnp.float32)
        vals, idx = jax.lax.top_k(lf, 2)
        at_gen = jnp.take_along_axis(lf, generated[:, None], axis=1)[:, 0]
        return idx[:, 0], vals[:, 0], vals[:, 1], at_gen
    return reference


def reference_check(reference, params, reqs, label):
    """Each generated token must be the teacher-forced reference's argmax.
    Where it is not, its reference logit must lie within GAP_ULPS bf16
    ulps of the top one: a near-tie that bf16 rounding may break either
    way. Such positions are counted as exempt."""
    import jax.numpy as jnp
    import numpy as np

    total = equal = exempt = near_ties = 0
    for r in reqs:
        tokens = jnp.asarray([r.prompt + r.generated], jnp.int32)
        gen = np.asarray(r.generated)
        idx, v1, v2, at_gen = (np.asarray(a) for a in reference(
            params, tokens, jnp.asarray(gen, jnp.int32)))
        # bf16 keeps 8 significant bits: one ulp at |v| is 2^(e - 7)
        tol = GAP_ULPS * np.exp2(
            np.floor(np.log2(np.maximum(np.abs(v1), 1e-30))) - 7)
        same = idx == gen
        bad = ~same & (v1 - at_gen > tol)
        check(not bad.any(),
              f"{label} request {r.req_id}: generated tokens differ from "
              f"the reference argmax at positions {np.nonzero(bad)[0]}, "
              f"{(v1 - at_gen)[bad]} below the top logit "
              f"(tolerance {tol[bad]})")
        total += len(gen)
        equal += int(same.sum())
        exempt += int((~same).sum())
        near_ties += int((v1 - v2 <= tol).sum())
    # a served path that is wrong picks tokens far below the top logit; one
    # that is right matches the argmax wherever bf16 does not tie
    check(equal >= MIN_EQUAL * total,
          f"{label}: only {equal} of {total} generated tokens equal the "
          f"reference argmax")
    print(f"[{label}] reference: {equal} of {total} generated tokens equal "
          f"the teacher-forced argmax; {exempt} exempt, each within "
          f"{GAP_ULPS} bf16 ulps of the top logit; {near_ties} positions "
          f"had a top-2 gap within that tolerance")


def serve_phase(cfg, rcfg, mesh, rng, key, clock):
    import jax
    from repro.control.controller import RateController
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import TenantScheduler

    t0, c0 = time.perf_counter(), clock.seconds
    sched = TenantScheduler(policy="wfq", charge_prompt=True)
    ctrl = RateController(CAPACITY, alpha=0.6)
    ctrl.attach_scheduler(sched)
    eng = ServeEngine(cfg, rcfg, mesh, key=key, batch_slots=SLOTS,
                      max_seq=MAX_SEQ, scheduler=sched, controller=ctrl,
                      control_every=4)
    reqs = make_requests(rng, n=12, tenants=3, vocab=cfg.vocab_size)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    jax.block_until_ready(eng.caches)
    billed = check_completed(reqs, eng.completed,
                             lambda t: sched.served_tokens.get(t, 0))
    check(ctrl.ticks > 0, "the rate controller never allocated")
    print(f"[serve] {len(reqs)} requests, {eng.decode_steps} decode steps, "
          f"billed per tenant {billed}, controller ticks {ctrl.ticks}")
    smoke_figures("serve", t0, c0, clock)
    return eng, reqs


def fabric_phase(cfg, rcfg, mesh, base, rng, clock):
    import jax
    from repro.control.controller import RateController
    from repro.serve.cluster import EngineCluster
    from repro.serve.engine import ServeEngine
    from repro.serve.replay import swap_live_stack
    from repro.serve.scheduler import TenantScheduler

    t0, c0 = time.perf_counter(), clock.seconds
    ctrl = RateController(CAPACITY, alpha=0.6)
    engs = []
    for _ in range(2):
        eng = ServeEngine(cfg, rcfg, mesh, params=base.params,
                          batch_slots=SLOTS, max_seq=MAX_SEQ,
                          scheduler=TenantScheduler(policy="wfq",
                                                    charge_prompt=True))
        # same config and cache shapes: share the compiled stack
        eng._prefill, eng._decode = base._prefill, base._decode
        engs.append(eng)
    cluster = EngineCluster(engs, ctrl, control_every=4)
    reqs = make_requests(rng, n=24, tenants=4, vocab=cfg.vocab_size,
                         first_id=100)
    for r in reqs:
        cluster.submit(r)

    def busy():
        return cluster.draining or any(
            cluster.engine_load(k) for k in range(len(cluster.engines)))

    steps = 0
    for _ in range(2):
        cluster.step()
        steps += 1
    # a tenant with both queued and in-flight work moves live
    src_tenants = [t for t, k in cluster.placement.items() if k == 0]
    tenant = max(src_tenants,
                 key=lambda t: engs[0].scheduler.pending(t))
    queued = engs[0].scheduler.pending(tenant)
    inflight = engs[0].inflight(tenant)
    check(queued > 0 and inflight > 0,
          f"tenant {tenant} has {queued} queued, {inflight} in flight: "
          f"not a live migration")
    rec = cluster.migrate(tenant, 1)
    print(f"[fabric] migrate tenant {tenant} 0 -> 1 with {rec.queued_moved} "
          f"queued and {rec.inflight_at_move} in flight")
    while cluster.draining:
        cluster.step()
        steps += 1
    check(busy(), "traffic ran dry before the stack swap")
    swap = swap_live_stack(cluster, "serve")
    check(swap.old_stack.endswith("[wfq]") and swap.new_stack.endswith("[rr]"),
          f"swap went {swap.old_stack} -> {swap.new_stack}")
    print(f"[fabric] swap engine {swap.engine} {swap.old_stack} -> "
          f"{swap.new_stack} under traffic")
    while busy():
        cluster.step()
        steps += 1
        check(steps < 10_000, "the cluster did not drain")
    jax.block_until_ready([e.caches for e in cluster.engines])
    for t in sorted(cluster.placement):
        cluster.assert_ledger_conservation(t)
    billed = check_completed(reqs, cluster.completed,
                             cluster.tenant_served_tokens)
    check(sum(len(r.generated) for r in cluster.completed)
          == NEW_TOKENS * len(reqs), "tokens were dropped")
    print(f"[fabric] {len(reqs)} requests over 2 engines in {steps} cluster "
          f"steps, ledger conserved for tenants {sorted(billed)}, billed "
          f"{billed}")
    smoke_figures("fabric", t0, c0, clock)
    return reqs


def nsm_phase(seed):
    """NSM collective stacks on a (pod=2, data=2) mesh of four chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.core import make_engine, nk_psum, use_engine, get_nsm
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=2, model=1, pod=2)
    ids = sorted(d.id for d in mesh.devices.flat)
    check(len(set(ids)) == 4, f"mesh devices {ids}: not four distinct chips")
    axes = ("pod", "data")
    spec = P(axes, None)
    # 2 MiB per shard: above the ring policy's 1 MiB threshold
    x = jax.random.normal(jax.random.PRNGKey(seed), (4096, 512), jnp.float32)

    def run(f, in_spec, out_spec, **kw):
        out = jax.jit(shard_map(f, mesh=mesh, in_specs=in_spec,
                                out_specs=out_spec, **kw))(x)
        out.block_until_ready()
        check(len(out.sharding.device_set) == 4,
              f"output lives on {len(out.sharding.device_set)} devices")
        return np.asarray(out)

    ref = run(lambda v: jax.lax.psum(v, axes), spec, spec)
    for policy, tol in (("xla", 1e-6), ("ring", 1e-5),
                        ("hierarchical", 1e-5), ("compressed", 2e-2),
                        ("shm-first", 1e-6)):
        eng = make_engine(mesh, policy)

        def f(v, eng=eng):
            with use_engine(eng):
                return nk_psum(v, axes, gradient=True)
        out = run(f, spec, spec)
        np.testing.assert_allclose(out, ref, rtol=tol,
                                   atol=tol * float(np.abs(ref).max()))
        check(eng.total_bytes() > 0, f"{policy}: the ledger recorded nothing")
        routes = sorted({nsm for _, nsm in eng.route_log})
        err = float(np.max(np.abs(out - ref)))
        print(f"[nsm] psum policy={policy} routed={routes} bytes="
              f"{eng.total_bytes()} max|err|={err!r} (tol {tol} x max)")

    ring = get_nsm("ring")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for ax in axes:
        gathered = run(
            lambda v, ax=ax: ring.all_gather(v, (ax,), axis_sizes=sizes,
                                             axis=0),
            P(ax, None), P(None, None), check_vma=False)
        native = run(
            lambda v, ax=ax: jax.lax.all_gather(v, ax, axis=0, tiled=True),
            P(ax, None), P(None, None), check_vma=False)
        np.testing.assert_allclose(gathered, native, rtol=1e-6, atol=1e-6)
        scattered = run(
            lambda v, ax=ax: ring.reduce_scatter(v, (ax,), axis_sizes=sizes,
                                                 axis=0),
            P(None, None), P(ax, None))
        native = run(
            lambda v, ax=ax: jax.lax.psum_scatter(v, ax, scatter_dimension=0,
                                                  tiled=True),
            P(None, None), P(ax, None))
        np.testing.assert_allclose(scattered, native, rtol=1e-6, atol=1e-6)
        print(f"[nsm] ring all_gather and reduce_scatter over {ax!r} match "
              f"the native collectives")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the NSM collective phase on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={jax.device_count()}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform is {dev.platform!r}); "
              f"this run measures the chip and does not fall back",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    check(len(devices) >= want, f"{len(devices)} chips; this run needs {want}")

    from repro.launch.compile_cache import configure_compile_cache
    print(f"compile cache: {configure_compile_cache()}")
    import numpy as np
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        nsm_phase(args.seed)
    else:
        from repro.configs import RunConfig, get_config
        from repro.launch.mesh import make_single_device_mesh

        cfg = get_config(MODEL)
        rcfg = RunConfig()
        mesh = make_single_device_mesh()
        rng = np.random.default_rng(args.seed)
        print(f"model: {cfg.name} layers={cfg.num_layers} d_model="
              f"{cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
              f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} {cfg.param_dtype}")
        eng, reqs = serve_phase(cfg, rcfg, mesh, rng,
                                jax.random.PRNGKey(args.seed), clock)
        reference = make_reference(cfg, rcfg, mesh)
        reference_check(reference, eng.params, reqs, "serve")
        # the fabric's engines reuse the weights and the compiled stack;
        # the serve engine's own KV-cache is no longer needed
        eng.caches = None
        fab = fabric_phase(cfg, rcfg, mesh, eng, rng, clock)
        reference_check(reference, eng.params, fab, "fabric")
    smoke_figures("total", t0, 0.0, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
