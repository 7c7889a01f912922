"""Multi-tenant serving engine: continuous batching over shared decode steps.

The NetKernel multiplexing story (use case 1) in serving terms: one engine
("NSM") serves requests from many tenants ("VMs"). Decode slots are the
shared resource; the TenantScheduler (CoreEngine control plane) decides
admission with fairness/rate policies; weights are shared by all tenants of
the same model (the shared-memory use case — tenants never hold their own
copy). Model code is untouched: prefill/decode are the same pure functions
the dry-run lowers for 256-chip meshes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig, RunConfig
from repro.distribution.sharding import ShardingCtx, init_params
from repro.fabric import SchedulerServeModule
from repro.kernels.decode_attention import blocks_read
from repro.models.model import (
    build_schedule, cache_schema, decode_kernel_layers,
    decode_writes_in_place, forward_decode, forward_prefill, model_schema,
)
from repro.obs import tracing
from repro.serve.scheduler import Request, TenantScheduler


# the counts an expert layer that holds a share returns (``models.moe``),
# summed over its layers, and the counters that total them
ROUTED = {"moe_assignments": "nk_moe_assignments_total",
          "moe_assignments_held": "nk_moe_assignments_held_total",
          "moe_experts_touched": "nk_moe_experts_touched_total"}


def lowering_platform(mesh) -> str:
    """The platform the engine's programs lower for: that of the mesh's
    devices, or of JAX's default device where there is no mesh."""
    device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return device.platform


def _install_slot(caches, one, slot):
    """Write a batch-1 prefill's cache ``one`` into slot ``slot`` of the
    engine's ``caches``. Every cache leaf (dense K/V, the MLA latent, SSM
    state; scanned, unrolled and ring segments alike) is stacked as
    ``[layers, slots, ...]``, so the slot is axis 1 of each."""
    return jax.tree.map(
        lambda big, o: lax.dynamic_update_slice_in_dim(
            big, o.astype(big.dtype), slot, axis=1),
        caches, one)


# the cache is donated and the slot traced: the update lands in the engine's
# own buffers, with no second cache alive, and one program per cache shape
# serves every slot of every engine (cluster replicas and swapped-in
# engines included)
install_slot = jax.jit(_install_slot, donate_argnums=0)


@dataclass
class Slot:
    active: bool = False
    req: Optional[Request] = None
    pos: int = 0           # next write position (== tokens so far - 1)
    remaining: int = 0


class ServeEngine(SchedulerServeModule):
    """Slot-based continuous batching engine (greedy decoding).

    Implements the serve-plane ``StackModule`` protocol (repro.fabric)
    via ``SchedulerServeModule``: tenant export/import delegate to the
    scheduler, ``billed_ground_truth`` reads completed requests + live
    slots, and ``suspend``/``resume`` make parking a real memory saving —
    suspend drops the KV-cache and the slot table; resume
    re-materializes the cache lazily from the shared ``cache_schema`` on
    the first admission after unpark.
    """

    def __init__(self, cfg: ModelConfig, rcfg: RunConfig, mesh, params=None,
                 *, batch_slots: int = 8, max_seq: int = 256,
                 scheduler: Optional[TenantScheduler] = None, key=None,
                 controller=None, control_every: int = 4):
        """``batch_slots``: concurrent decode slots (the shared resource);
        ``max_seq``: KV-cache length in tokens; ``params``: share another
        engine's weights (the shared-memory story — cluster replicas pass
        the first engine's) or None to init fresh; ``controller``:
        optional management-plane hook ticked every ``control_every``
        steps (must be None when the engine joins an EngineCluster, which
        ticks the shared controller itself)."""
        self.cfg, self.rcfg, self.mesh = cfg, rcfg, mesh
        self.B, self.max_seq = batch_slots, max_seq
        self.shd = ShardingCtx(mesh)
        self.scheduler = scheduler or TenantScheduler()
        # management plane: anything with tick(now) — typically a
        # repro.control.RateController attached to self.scheduler. Rates it
        # pushes take effect on the very next admission decision.
        self.controller = controller
        self.control_every = max(int(control_every), 1)
        self.params = params if params is not None else init_params(
            model_schema(cfg, mesh),
            key if key is not None else jax.random.PRNGKey(0))
        self.slots = self._make_slots()
        self.caches = None
        self._cache_nbytes = 0
        self._init_caches()
        self.steps = 0
        self.decode_steps = 0
        self.installs = 0      # prefill caches written into a slot
        self.completed: List[Request] = []
        # cache segments whose new K/V rows the decode program writes in
        # place (the rest take the masked select): fixed by the model and
        # the mesh, so counted once here
        schema = cache_schema(cfg, batch_slots, max_seq)
        self.decode_inplace_segments = sum(
            decode_writes_in_place(seg, c, self.shd, rcfg)
            for seg, c in zip(build_schedule(cfg), schema))
        # layers that keep a recurrent state in the cache, and the bytes of
        # it: the f32 state and the conv tails, every slot
        ssm = [c for c in schema if c is not None and "state" in c]
        self.ssm_layers = sum(c["state"].shape[0] for c in ssm)
        self.ssm_state_bytes = sum(
            int(np.prod(d.shape)) * np.dtype(d.dtype).itemsize
            for c in ssm for k, d in c.items()
            if k == "state" or k.startswith("conv_"))
        # layers whose decode attends with the flash-decode kernel, on the
        # platform the programs lower for; and the K blocks it has read and
        # that a read of every position would have taken (V reads as
        # many), summed from the positions each step sends
        self.platform = lowering_platform(mesh)
        self.decode_kernel_layers = decode_kernel_layers(
            cfg, self.shd, rcfg, max_seq, self.platform)
        self._kv_blocks = np.zeros(2, np.int64)

        # an expert layer that holds a share of the router's experts: its
        # programs also sum the routed counts (ROUTED) on the device
        self.counts_routing = cfg.moe is not None and cfg.moe.holds_share
        self._routed = None
        self._routed_total = np.zeros(len(ROUTED), np.int64)

        self._programs(max_seq)

    def _programs(self, max_seq: int) -> None:
        """The jitted prefill and (cache-donating) decode programs. Where
        the expert layers hold a share, each program also takes the
        running sum of the routed counts (``ROUTED``) as a last argument
        and returns it with its own counts added, so the sum stays on the
        device (no host sync per step; ``counters()`` reads it); the
        engine's ``_prefill`` and ``_decode`` keep their signatures and
        carry that sum in ``self._routed``."""
        cfg_, rcfg_, shd_ = self.cfg, self.rcfg, self.shd

        def summed(out, routed):
            if not routed:
                return out
            *out, got = out
            return (*out, routed[0] + jnp.stack([got[k] for k in ROUTED]))

        def _prefill(params, tokens, *routed):
            return summed(forward_prefill(
                params, tokens, cfg_, shd_, rcfg_, max_seq=max_seq,
                return_counts=bool(routed)), routed)

        def _decode(params, caches, tokens, pos, *routed):
            logits, caches, *got = forward_decode(
                params, caches, tokens, pos, cfg_, shd_, rcfg_,
                return_counts=bool(routed))
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return summed((nxt, caches, *got), routed)

        prefill = jax.jit(_prefill)
        decode = jax.jit(_decode, donate_argnums=(1,))
        if not self.counts_routing:
            self._prefill, self._decode = prefill, decode
            return
        self._routed = self._zero_counts()

        def counted(program):
            def call(*args):
                *out, self._routed = program(*args, self._routed)
                return tuple(out)
            return call

        self._prefill, self._decode = counted(prefill), counted(decode)

    def _zero_counts(self):
        """Zero counts placed as the programs return them (replicated over
        the mesh), so that the programs see one argument layout and never
        compile again for it."""
        z = jnp.zeros(len(ROUTED), jnp.int32)
        if self.mesh is None:
            return z
        return jax.device_put(z, NamedSharding(self.mesh, PartitionSpec()))

    # -- StackModule buffer hooks (the suspend/resume memory story) --------
    def _make_slots(self):
        return [Slot() for _ in range(self.B)]

    def _init_caches(self) -> None:
        """(Re-)materialize the KV-cache from the shared ``cache_schema``
        — at construction, and lazily on the first admission after a
        ``resume`` (an unparked engine with no traffic stays cache-free).
        Slot caches are fully overwritten by prefill on admission, so a
        re-init is bit-identical to never having suspended."""
        self.caches = init_params(
            cache_schema(self.cfg, self.B, self.max_seq),
            jax.random.PRNGKey(1))
        self._cache_nbytes = sum(
            int(x.size) * x.dtype.itemsize
            for x in jax.tree.leaves(self.caches))

    def _cache_bytes(self) -> int:
        return 0 if self.caches is None else self._cache_nbytes

    def _release_buffers(self) -> None:
        self.caches = None

    def counters(self) -> Dict[str, float]:
        """The decode program's static facts, the SSM state the cache
        holds, the cache blocks its
        attention kernel has read so far and, for an expert layer that
        holds a share, the routed counts of every prefill and decode so
        far (Prometheus naming):
        ``registry.register_provider(engine, name="engine")``.

        The counts are summed on the device inside the programs and read
        (one host sync) only here; the device's running sum then restarts
        from zero, so it cannot wrap between two reads of 2**31
        assignments."""
        out = {"nk_decode_cache_inplace_segments":
               float(self.decode_inplace_segments)}
        # the kernel's counters exist where it can run: the gauge where the
        # programs lower for a TPU, the blocks where some layer reads them
        if self.platform == "tpu":
            out["nk_decode_attention_kernel_layers"] = \
                float(self.decode_kernel_layers)
        if self.decode_kernel_layers:
            out["nk_decode_kv_blocks_read_total"] = float(self._kv_blocks[0])
            out["nk_decode_kv_blocks_total"] = float(self._kv_blocks[1])
        if self.ssm_layers:
            out["nk_ssm_layers"] = float(self.ssm_layers)
            out["nk_ssm_state_bytes"] = float(self.ssm_state_bytes)
        if self.counts_routing:
            self._routed_total += np.asarray(self._routed)
            self._routed = self._zero_counts()
            out["nk_moe_experts_held"] = float(self.cfg.moe.num_experts)
            for name, v in zip(ROUTED.values(), self._routed_total):
                out[name] = float(v)
        return out

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        """Queue one request for admission (delegates to the scheduler)."""
        self.scheduler.submit(req)

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if not s.active:
                return i
        return None

    def _admit(self, now=None):
        region = tracing.TRACER.region
        with region("engine", "admit"):
            while True:
                i = self._free_slot()
                if i is None:
                    return
                req = self.scheduler.next_request(now)
                if req is None:
                    return
                if self.caches is None:
                    # lazy resume: the KV-cache dropped at park
                    # re-materializes only when a request actually lands
                    self._init_caches()
                prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
                with region("engine", "prefill"):
                    last_logits, caches1 = self._prefill(self.params, prompt)
                # install the single-sequence cache into slot i, in place
                with region("engine", "install"):
                    self.caches = install_slot(self.caches, caches1,
                                               np.int32(i))
                self.installs += 1
                with region("engine", "first_token"):
                    first = int(jnp.argmax(last_logits[0]))
                req.generated.append(first)
                req.admit_time = time.monotonic() if now is None else now
                self.observe_admitted(req)
                # prompt tokens + the first generated token: prefill
                # produced both, so the ledger must bill them here — decode
                # steps only account the tokens they themselves produce
                # (leaving the prefill token out undercounts every request
                # by one and caps measured throughput below the enforced
                # allocation)
                self.scheduler.account(req.tenant_id, len(req.prompt) + 1)
                if req.max_new_tokens <= 1:
                    # prefill already produced the only requested token; a
                    # slot would run one decode step anyway and
                    # over-generate (and over-bill) past the bucket's
                    # prompt+max_new price
                    req.finish_time = req.admit_time
                    self.completed.append(req)
                    self.observe_finished(req)
                    continue
                self.slots[i] = Slot(active=True, req=req,
                                     pos=len(req.prompt),
                                     remaining=req.max_new_tokens - 1)

    def step(self, now=None) -> int:
        """Admit + one decode step for all active slots. Returns #active."""
        if self.suspended:
            raise RuntimeError(
                "engine is suspended (parked); resume() before stepping")
        region = tracing.TRACER.region
        with region("engine", "step"):
            self.steps += 1
            # tick before admission (and before the no-work early return):
            # a fully-throttled engine must still get rate updates or it
            # livelocks
            if self.controller is not None and \
                    self.steps % self.control_every == 0:
                self.controller.tick(time.monotonic() if now is None
                                     else now)
            self._admit(now)
            active = [i for i, s in enumerate(self.slots) if s.active]
            if not active:
                return 0
            with region("engine", "prepare"):
                tokens = np.zeros((self.B, 1), np.int32)
                pos = np.zeros((self.B,), np.int32)
                for i, s in enumerate(self.slots):
                    if s.active:
                        tokens[i, 0] = s.req.generated[-1]
                        pos[i] = s.pos
                if self.decode_kernel_layers:
                    self._kv_blocks += self.decode_kernel_layers * np.array(
                        blocks_read(pos, self.max_seq))
                tokens, pos = jnp.asarray(tokens), jnp.asarray(pos)
            with region("engine", "decode"):
                nxt, self.caches = self._decode(self.params, self.caches,
                                                tokens, pos)
            with region("engine", "readback"):
                nxt = np.asarray(nxt)
            with region("engine", "commit"):
                for i in active:
                    s = self.slots[i]
                    s.req.generated.append(int(nxt[i]))
                    s.pos += 1
                    s.remaining -= 1
                    self.scheduler.account(s.req.tenant_id, 1)
                    if s.remaining <= 0 or s.pos >= self.max_seq - 1:
                        s.req.finish_time = time.monotonic() if now is None \
                            else now
                        self.completed.append(s.req)
                        self.observe_finished(s.req)
                        self.slots[i] = Slot()
            self.decode_steps += 1
            return len(active)

    def run_until_drained(self, max_steps: int = 10000) -> Dict:
        n = 0
        while (self.scheduler.pending() or
               any(s.active for s in self.slots)) and n < max_steps:
            self.step()
            n += 1
        return {"decode_steps": self.decode_steps,
                "completed": len(self.completed),
                "shares": self.scheduler.shares()}
