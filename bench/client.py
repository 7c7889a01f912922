"""The system under test, and the open-loop client that drives it.

``Serving`` builds the program's serving path from a configuration file:
one ``ServeEngine`` under a WFQ ``TenantScheduler`` with an attached
``RateController``, on one chip, with the benchmark's weights. It wraps
the calls that the measured window drives, without changing them, to
record host spans (``jax.profiler.TraceAnnotation``, on the profiler's
clock) and the client's own records:

  bench.step          ServeEngine.step, as the client calls it
  bench.admit         ServeEngine._admit (scheduler pick, prefill, cache
                      install, first-token read-back)
  bench.next_request  TenantScheduler.next_request; stamps the pick time
  bench.tick          RateController.tick; its host seconds
  bench.prefill       the prefill program's dispatch; prompt tokens
  bench.decode        the decode program's dispatch; active slot positions
  bench.readback      the decode step's host read-back of next tokens
  bench.submit        the client handing due requests to the engine

``run_open_loop`` submits each request when it falls due, calls ``step``,
and stamps every token with the return of the step that delivered it.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from bench.traffic import Req

clock = time.perf_counter


@dataclass
class StepRec:
    idx: int
    start: float
    end: float = 0.0
    prefill_lens: List[int] = field(default_factory=list)
    decode_positions: List[int] = field(default_factory=list)


@dataclass
class Records:
    steps: List[StepRec] = field(default_factory=list)
    ticks: List[tuple] = field(default_factory=list)     # (start, seconds)
    late: List[float] = field(default_factory=list)      # submit - due


class _Readback:
    """Stands in for the decode step's output so that the engine's host
    read-back (``np.asarray``) runs inside a span of its own."""

    def __init__(self, arr, annotate):
        self._arr, self._annotate = arr, annotate

    def __array__(self, dtype=None, copy=None):
        with self._annotate("bench.readback"):
            out = np.asarray(self._arr)
        return out if dtype is None else out.astype(dtype)


def program_config(m: Dict):
    """The program's ModelConfig for the configuration file's model: the
    program's own entry (``program_name``) with the file's sizes applied.
    A dict under ``moe`` or ``ssm`` is applied to that sub-config, field
    by field. A key that the program has no field for raises: a published
    size that the program cannot take shows, and does not vanish."""
    from repro.configs import get_config
    from repro.configs.base import MoEConfig, SSMConfig
    cfg = get_config(m["program_name"])
    names = {f.name for f in dataclasses.fields(cfg)}
    subs = {"moe": MoEConfig, "ssm": SSMConfig}
    over = {}
    for k, v in m.items():
        if k in ("program_name", "name"):
            continue
        if k == "mla":
            raise ValueError(
                "model key 'mla': the program attaches its latent attention "
                "by name (repro.configs.base.MLA_BY_NAME), so a file cannot "
                "set it until the program takes an mla field")
        if k not in names:
            raise ValueError(f"model key {k!r} is no field of the program's "
                             f"ModelConfig")
        if k in subs and isinstance(v, dict):
            sub_names = {f.name for f in dataclasses.fields(subs[k])}
            bad = sorted(set(v) - sub_names)
            if bad:
                raise ValueError(f"model key {k}.{bad[0]} is no field of "
                                 f"the program's {subs[k].__name__}")
            sub = getattr(cfg, k)
            v = subs[k](**v) if sub is None else dataclasses.replace(sub, **v)
        if getattr(cfg, k) != v:
            over[k] = v
    return dataclasses.replace(cfg, **over) if over else cfg


class Serving:
    def __init__(self, conf: Dict, mix_weights: Dict[int, float], params):
        import jax
        from repro.configs import RunConfig
        from repro.control.controller import RateController
        from repro.launch.mesh import make_single_device_mesh
        from repro.serve.engine import ServeEngine
        from repro.serve.scheduler import Request, TenantScheduler

        self._annotate = jax.profiler.TraceAnnotation
        self.Request = Request
        e = conf["engine"]
        self.cfg = program_config(conf["model"])
        self.sched = TenantScheduler(policy=e["policy"],
                                     charge_prompt=e["charge_prompt"])
        for t, w in sorted(mix_weights.items()):
            self.sched.add_tenant(t, weight=w)
        self.ctrl = RateController(e["controller_capacity"],
                                   alpha=e["controller_alpha"])
        self.ctrl.attach_scheduler(self.sched)
        self.eng = ServeEngine(
            self.cfg, RunConfig(), make_single_device_mesh(), params=params,
            batch_slots=e["batch_slots"], max_seq=e["max_seq"],
            scheduler=self.sched, controller=self.ctrl,
            control_every=e["control_every"])
        self.rec = Records()
        self.by_served: Dict[int, Req] = {}
        self.inflight: List[Req] = []
        self.submitted: List[Req] = []
        self._wrap()

    # -- spans and records around the program's own calls ------------------
    def _wrap(self):
        ann, rec, eng = self._annotate, self.rec, self.eng
        pick, tick = self.sched.next_request, self.ctrl.tick
        admit, prefill, decode = eng._admit, eng._prefill, eng._decode

        def next_request(now=None):
            with ann("bench.next_request"):
                r = pick(now)
            if r is not None:
                req = self.by_served[id(r)]
                req.picked = clock()
                self.inflight.append(req)
            return r

        def tick_(now=None):
            t0 = clock()
            with ann("bench.tick"):
                out = tick(now)
            rec.ticks.append((t0, clock() - t0))
            return out

        def admit_(now=None):
            with ann("bench.admit"):
                return admit(now)

        def prefill_(params, tokens):
            if rec.steps:
                rec.steps[-1].prefill_lens.append(int(tokens.shape[1]))
            with ann("bench.prefill"):
                return prefill(params, tokens)

        def decode_(params, caches, tokens, pos):
            if rec.steps:
                rec.steps[-1].decode_positions = [
                    s.pos for s in eng.slots if s.active]
            with ann("bench.decode"):
                nxt, caches = decode(params, caches, tokens, pos)
            return _Readback(nxt, ann), caches

        self.sched.next_request = next_request
        self.ctrl.tick = tick_
        eng._admit = admit_
        eng._prefill = prefill_
        eng._decode = decode_

    # -- the client ----------------------------------------------------------
    def submit(self, req: Req, at: float):
        r = self.Request(tenant_id=req.tenant, prompt=req.prompt.tolist(),
                         max_new_tokens=req.out_len, req_id=req.rid,
                         arrival=time.monotonic())
        req.served = r
        self.by_served[id(r)] = req
        self.submitted.append(req)
        self.rec.late.append(at - req.due)
        self.eng.submit(r)

    def step(self):
        k = len(self.rec.steps)
        sr = StepRec(idx=k, start=clock())
        self.rec.steps.append(sr)
        with self._annotate("bench.step", step=k):
            self.eng.step()
        sr.end = t = clock()
        still = []
        for req in self.inflight:
            gen = req.served.generated
            for _ in range(len(gen) - len(req.token_times)):
                req.token_times.append(t)
            if req.served.finish_time < 0:
                still.append(req)
        self.inflight = still

    def idle(self) -> bool:
        return not self.sched.pending() and \
            not any(s.active for s in self.eng.slots)


def run_open_loop(srv: Serving, reqs: List[Req], t_end: float,
                  start_index: int = 0) -> int:
    """Submit each request of ``reqs`` (due times on the client clock) as
    it falls due and step the engine until ``t_end``. Returns the index of
    the first request not yet submitted."""
    i = start_index
    n = len(reqs)
    ann = srv._annotate
    while True:
        now = clock()
        if now >= t_end:
            return i
        if i < n and reqs[i].due <= now:
            with ann("bench.submit"):
                while i < n and reqs[i].due <= now:
                    srv.submit(reqs[i], now)
                    i += 1
        if srv.idle():
            nxt = reqs[i].due if i < n else t_end
            time.sleep(max(0.0, min(nxt, t_end) - clock()))
            continue
        srv.step()


def drain(srv: Serving, limit_s: float = 120.0):
    t_stop = clock() + limit_s
    while not srv.idle() and clock() < t_stop:
        srv.step()
