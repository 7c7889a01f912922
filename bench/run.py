"""Run one cell of BENCHMARK.json once, on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); its metrics are readers in
``bench/metrics/<metric>.py``. The configuration names its architecture
(``bench/arch/<arch>.py``: weight layout, the program's parameter tree,
operation counts, smoke widths) and its plain reference
(``bench/reference/<reference>.py``). Every such file is found by its name
under the checkout's ``bench/``, so a configuration, a mix or a metric of
a new kind is added as new files. A run:

  1. names the device on its first line, and exits 2 without a result
     where JAX finds no TPU or fewer chips than the cell asks for;
  2. makes the weights from the seed on the device, builds the serving
     path, and warms every shape the mix uses: each prompt length's
     prefill, the cache install into every slot, and the decode step;
  3. runs the mix open-loop through a warm-up span, then measures for
     ``--seconds`` (with ``--trace 1`` under the profiler, with the
     program's own regions on: ``repro.obs.tracing.ProfilerTracer``);
  4. reads the device's peak memory, frees the engine, and checks what the
     window served against the plain reference (bench/check.py);
  5. prints the checks, each beside its limit, as the last lines of
     standard error, and one JSON result as the last line of standard
     output.

``--control 1`` also reads the float8 control over the same sample and
judges it in the served tokens' place, so the run is not correct; and
``--sweep r1,r2,..`` measures each rate (requests/s, all streams) in turn
after one set-up, for finding the knee; neither is part of a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


class Refused(Exception):
    """This machine cannot run the cell: no result is printed."""


def load_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = json.loads((ROOT / confs[cell["config"]]["file"]).read_text())
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())

    def applies(metric):
        return name in metric.get("workloads", [name])
    return (spec, cell, conf, mix,
            [m for m in spec["end_to_end"] if applies(m)],
            [m for m in spec["per_layer"] if applies(m)])


@functools.lru_cache(maxsize=None)
def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` under the checkout, loaded once by its
    path (a metric's name may hold dots): ``arch``, ``reference`` or
    ``metrics``."""
    return _load(ROOT / "bench" / kind / f"{name}.py", f"bench.{kind}.{name}")


def reader(metric_name: str):
    """The ``read`` function of ``bench/metrics/<metric_name>.py``."""
    return module("metrics", metric_name).read


class CompileCounter:
    """Counts JAX compiles (backend compiles and persistent-cache loads)."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs


class Ctx:
    """What a metric reader reads: the window's edges (client clock), the
    requests and the client's records; ``model`` (the configuration's
    sizes) and ``arch`` (its ``bench/arch`` module: ``decode_call``,
    ``prefill_flops``); ``peak`` (``bench/peaks.json``); the engine's
    counters (``ServeEngine.counters()``) at the window's open and close;
    and in a traced run ``trace`` (``bench/trace.py``'s record: device ops
    and modules, ``bench.*`` spans under ``host``, the program's ``nk.*``
    regions under ``program``; ``trace.op_scopes`` gives the ops their
    name scopes) with ``trace_window``, else None."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._step_spans = None

    def _spans(self):
        if self._step_spans is None:
            from bench import trace
            self._step_spans = trace.step_spans(self.trace)
        return self._step_spans

    def step_of(self, t):
        """Index of the step whose host span holds trace time t."""
        from bench import trace
        return trace.step_at(self._spans(), t)

    def steps_in_trace(self):
        lo, hi = self.trace_window
        return [self.steps[k] for s, e, k in self._spans()
                if s >= lo and e <= hi and 0 <= k < len(self.steps)]


def build(conf, mix, seed, counter, log, arch):
    """Weights, the serving path, and every shape the mix uses, warm."""
    import jax
    from bench import traffic, weights
    from bench.client import Serving, drain
    m = conf["model"]
    w = weights.make_weights(arch.layout(m), m["param_dtype"], seed)
    jax.block_until_ready(w)
    log(f"weights: {sum(x.size for x in jax.tree.leaves(w))} parameters "
        f"from the seed")
    srv = Serving(conf, traffic.weights(mix), arch.program_params(w, m))
    # one request per slot, cycling through the mix's prompt lengths and
    # its tenants: every prefill shape, the install into every slot, decode
    lens = traffic.prompt_lengths(mix)
    tenants = sorted(traffic.weights(mix))
    warm = []
    import numpy as np
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    for i in range(srv.eng.B):
        n = lens[i % len(lens)]
        warm.append(traffic.Req(rid=-1 - i, tenant=tenants[i % len(tenants)],
                                due=0.0, prompt_len=n, out_len=2,
                                prompt=rng.integers(0, m["vocab_size"], n,
                                                    dtype=np.int32)))
    from bench.client import clock
    for r in warm:
        r.due = clock()
        srv.submit(r, r.due)
    drain(srv)
    log(f"warm: {len(lens)} prompt lengths {lens}, {srv.eng.B} slots; "
        f"{counter.n} compiles so far ({counter.seconds:.1f} s)")
    return w, srv


def run_cell(args, cell, conf, mix, e2e, per_layer, peak, device, log):
    """Everything after the look for a chip. Returns the result dict."""
    import jax
    from bench import check, program_spans, trace, traffic
    from bench.client import clock, run_open_loop
    from repro.obs import tracing

    counter = CompileCounter()
    m = conf["model"]
    arch = module("arch", conf["arch"])
    w, srv = build(conf, mix, args.seed, counter, log, arch)

    reqs = traffic.schedule(mix, seed=args.seed, seconds=args.seconds,
                            vocab=m["vocab_size"], knee_rps=conf["knee_rps"])
    warm_s = float(mix["warmup_s"])
    t0 = clock()
    for r in reqs:
        r.due += t0
    w_open, w_close = t0 + warm_s, t0 + warm_s + args.seconds
    i = run_open_loop(srv, reqs, w_open)
    setup_s = clock() - T_START
    billed_open = dict(srv.sched.served_tokens)
    counters_open = srv.eng.counters()
    compiles_open = counter.n
    tdir = None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        prev_tracer = tracing.set_tracer(tracing.ProfilerTracer())
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            run_open_loop(srv, reqs, w_close, i)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
            tracing.set_tracer(prev_tracer)
    billed_close = dict(srv.sched.served_tokens)
    counters_close = srv.eng.counters()
    compiles_in_window = counter.n - compiles_open
    print(f"compiles_in_window: {compiles_in_window}", flush=True)
    late = sorted(x for x in srv.rec.late)
    due_in = [r for r in reqs if w_open <= r.due < w_close]
    print(f"window: {len(due_in)} requests due, {len(srv.rec.steps)} steps "
          f"in all; generator late by p50 "
          f"{late[len(late) // 2] if late else 0.0!r} s, max "
          f"{late[-1] if late else 0.0!r} s", flush=True)

    stats_ = jax.devices()[0].memory_stats() or {}
    dev = dict(device, memory_peak_bytes=int(stats_.get(
        "peak_bytes_in_use", 0)))

    ctx = Ctx(window=(w_open, w_close), requests=reqs, records=srv.rec,
              steps=srv.rec.steps, latency_tenants=set(
                  mix["latency_tenants"]),
              weights=traffic.weights(mix), billed_at_open=billed_open,
              billed_at_close=billed_close, setup_s=setup_s, model=m,
              arch=arch, peak=peak, counters_at_open=counters_open,
              counters_at_close=counters_close, trace=None,
              trace_window=None)
    breakdown = None
    if args.trace:
        rec = trace.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        win = trace.host_span(rec, "bench.window")
        ctx.trace, ctx.trace_window = rec, win
        lo, hi = win
        dev["busy_s"] = trace.busy_seconds(rec, lo, hi)
        dev["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": trace.top_ops(rec, lo, hi),
                     "idle_gaps": trace.idle_by_span(rec, lo, hi)}
        log(f"program idle: {json.dumps(program_spans.split(rec, lo, hi))}")
    metrics = {}
    for met in (per_layer if args.trace else e2e):
        v = reader(met["name"])(ctx)
        if v is not None:
            metrics[met["name"]] = {"value": float(v), "unit": met["unit"]}

    # the check: the engine's state goes first, so the reference fits
    led = check.ledger(srv.submitted, srv.sched)
    finished = [r for r in srv.submitted if r.rid >= 0
                and r.served.finish_time >= 0 and r.token_times
                and w_open <= r.token_times[-1] < w_close]
    sample = check.sample(finished, args.seed)
    attempted = len(due_in)
    srv.eng.caches = None
    del srv
    gc.collect()
    ref = module("reference", conf["reference"])
    t_ref = clock()
    g = check.gaps(ref, w, m, sample, conf["engine"]["max_seq"],
                   control=bool(args.control))
    log(f"reference: {len(sample)} requests, {g['tokens']} served tokens, "
        f"{g['equal']} equal to the reference's first token, "
        f"{clock() - t_ref:.1f} s")
    limit = conf["check"]["served_gap_max"]
    checks = {
        "served_gap_max": {"value": g["served_gap_max"], "limit": limit},
        "ledger_gap": {"value": led["ledger_gap"], "limit": 0},
        "lost": {"value": led["lost"], "limit": 0},
    }
    # a control run puts the float8 reference's tokens in the served
    # tokens' place: it is judged by the same limit, and must fail it
    judged = "control_gap_max" if args.control else "served_gap_max"
    if args.control:
        checks[judged] = {"value": g[judged], "limit": limit}
    per_request = g["control_per_request" if args.control else "per_request"]
    gap_ok = limit is not None and bool(sample) and g[judged] <= limit
    correct = gap_ok and led["ledger_gap"] == 0 and led["lost"] == 0
    failed = led["lost"] + (len(sample) if limit is None else sum(
        1 for x in per_request if x > limit))
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": int(failed), "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def sweep(args, conf, mix, log):
    """Offer each rate in turn after one set-up; print one line a rate."""
    import jax
    from bench import stats, traffic
    from bench.client import clock, run_open_loop
    counter = CompileCounter()
    m = conf["model"]
    _, srv = build(conf, mix, args.seed, counter, log,
                   module("arch", conf["arch"]))
    base = 0
    for k, rate in enumerate(float(x) for x in args.sweep.split(",")):
        one = dict(mix, streams=[dict(s, rate={"rps": rate})
                                 for s in mix["streams"]])
        reqs = traffic.schedule(one, seed=args.seed + k,
                                seconds=args.seconds, vocab=m["vocab_size"])
        for r in reqs:
            r.rid += base
        base += len(reqs)
        t0 = clock()
        for r in reqs:
            r.due += t0
        lo, hi = t0 + float(mix["warmup_s"]), t0 + float(mix["warmup_s"]) \
            + args.seconds
        i = run_open_loop(srv, reqs, lo)
        out_lo = srv.sched.pending() + len(srv.inflight)
        run_open_loop(srv, reqs, hi, i)
        out_hi = srv.sched.pending() + len(srv.inflight)
        due = [r for r in reqs if lo <= r.due < hi]
        done = [r for r in due if r.served is not None
                and r.served.finish_time >= 0]
        ttft = stats.ttft_samples(reqs, lo, hi, set(mix["latency_tenants"]))
        print(json.dumps({
            "rate_rps": rate, "due": len(due), "finished_of_due": len(done),
            "outstanding_at_open": out_lo, "outstanding_at_close": out_hi,
            "out_tok_per_s": stats.tokens_in_window(reqs, lo, hi)
            / args.seconds,
            "ttft_p50_s": stats.percentile(ttft, 50),
            "ttft_p95_s": stats.percentile(ttft, 95),
            "itl_p95_ms": (stats.percentile(
                stats.itl_samples(reqs, lo, hi), 95) or 0) * 1e3,
            "compiles": counter.n}), flush=True)
        for q in srv.sched.queues.values():
            q.clear()
        t_stop = clock() + 60
        while any(s.active for s in srv.eng.slots) and clock() < t_stop:
            srv.step()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)

    def log(msg):
        print(f"[{time.perf_counter() - T_START:7.1f} s] {msg}", flush=True)

    try:
        spec, cell, conf, mix, e2e, per_layer = load_cell(args.workload)
        import jax
        devices = jax.devices()
        d = devices[0]
        print(f"device: platform={d.platform} device_kind={d.device_kind} "
              f"device_count={len(devices)}", flush=True)
        if d.platform != "tpu":
            raise Refused(f"JAX platform is {d.platform!r}, not a TPU: this "
                          f"benchmark measures the chip and does not fall "
                          f"back")
        if len(devices) < cell["chips"]:
            raise Refused(f"{len(devices)} chips; the cell asks for "
                          f"{cell['chips']}")
        peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
        if d.device_kind not in peaks["devices"]:
            raise Refused(f"no peaks for device kind {d.device_kind!r} in "
                          f"bench/peaks.json")
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    # every program, eager ones too, goes to the persistent cache, so that
    # only a checkout's first run compiles
    from repro.launch.compile_cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.sweep:
        sweep(args, conf, mix, log)
        return 0
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices)}
    out = run_cell(args, cell, conf, mix, e2e, per_layer,
                   peaks["devices"][d.device_kind], device, log)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
