"""One run of a serving cell: ``ServingEngine`` under open-loop traffic.

A configuration whose ``builder`` is ``serving`` names an architecture id
of ``repro.configs.get_config`` (``arch``), the widths it is run at
(``model``, applied over the registry's entry) and the engine's
``ServeConfig`` (``serve``).  Its traffic (``bench/traffic/<traffic>.json``,
``kind`` ``open_loop``) gives the arrival rate and process, the prompt and
output length distributions, the lead-in before the window and the length
of the traced round.

Set-up makes the weights on the device from the seed in one jitted call,
builds the engine through its public API, and serves one request per
prefill bucket the traffic can reach, filling every slot, so every program
the window drives is compiled or loaded from the persistent cache.  The
arrival schedule then starts ``lead_in_s`` before the window, so the
engine enters the window loaded.  Each phase (lead-in, window, traced
round) serves the same multiset of prompt lengths, output lengths and
inter-arrival gaps for every seed, in an order drawn from the seed; token
ids are uniform over the vocabulary.

Every request is timed from when it was due.  The engine takes one
``max_new_tokens`` for all requests, so the loop ends each request at its
drawn length with ``RequestHandle.cancel()`` after the ``step()`` that
delivered its last token; tokens the pipeline makes past that point are
not counted.

``correct``: no request failed or timed out, no compile degraded, and for
``check.requests`` finished requests drawn from the seed (the one with the
most tokens among them) the plain reference of ``bench/references/
<config>.py``, teacher-forced over each prompt and its served tokens in
float32, puts no served token further below its own best logit than the
limit allows, as a share of the largest magnitude of that position's
logits (``logit_gap``).
"""
from __future__ import annotations

import contextlib
import gc
import json
import time
import zlib
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import numpy as np

from . import harness, serve_counts
from .harness import log

# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
@dataclass
class Request:
    """One scheduled request and what the host saw of it."""

    phase: str
    due: float  # seconds after the schedule's start
    prompt: np.ndarray
    target: int  # tokens it is served
    handle: Any = None
    state: str | None = None  # the handle's last state, once it is released
    submitted: float | None = None  # absolute host clock
    admit_step: float | None = None  # start of the step() that admitted it
    times: list[float] = field(default_factory=list)
    tokens: list[int] = field(default_factory=list)

    @property
    def finished(self) -> bool:
        return len(self.tokens) >= self.target

    @property
    def failed(self) -> bool:
        state = self.handle.state.value if self.handle is not None else self.state
        return state in ("failed", "timed_out")

    def deliver(self, tok: int, now: float) -> None:
        if len(self.tokens) < self.target:
            self.tokens.append(int(tok))
            self.times.append(now)


def stratified(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(k + 0.5) / n`` of a lognormal of
    the given median and sigma, rounded and clipped to ``[min, max]``."""
    from statistics import NormalDist

    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def gaps(arrivals: dict, rate: float, n: int, span: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles ``(k + 0.5) / n`` of a
    gamma renewal process of the given coefficient of variation, scaled to
    add up to ``span`` seconds (so the mean rate is exactly ``n / span``)."""
    from scipy.special import gammaincinv

    if arrivals["process"] != "gamma":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    shape = 1.0 / arrivals["cv"] ** 2
    g = gammaincinv(shape, (np.arange(n) + 0.5) / n) / (shape * rate)
    return g * (span / g.sum())


def schedule(traffic: dict, vocab: int, seed: int, seconds: float, trace: bool,
             rate: float | None = None) -> list[Request]:
    """The arrival schedule from the seed, sorted by due time."""
    rate = traffic["rate_per_s"] if rate is None else rate
    phases = {"lead_in": float(traffic["lead_in_s"]), "window": float(seconds)}
    if trace:
        phases["trace"] = float(traffic["trace_s"])
    reqs, t0 = [], 0.0
    for phase, span in phases.items():
        n = max(1, round(rate * span))
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, zlib.crc32(phase.encode())])
        due = t0 + np.concatenate([[0.0], np.cumsum(rng.permutation(
            gaps(traffic["arrivals"], rate, n, span)))[:-1]])
        plen = rng.permutation(stratified(traffic["prompt_tokens"], n))
        olen = rng.permutation(stratified(traffic["output_tokens"], n))
        for k in range(n):
            prompt = rng.integers(0, vocab, size=int(plen[k]), dtype=np.int32)
            reqs.append(Request(phase, float(due[k]), prompt, int(olen[k])))
        t0 += span
    return reqs


# ---------------------------------------------------------------------------
# the open loop
# ---------------------------------------------------------------------------
@dataclass
class Step:
    start: float
    occupied: int  # slots the step dispatched over (step()'s return)
    context: list[int]  # tokens each running request held when it returned


class OpenLoop:
    """Submits requests when due and steps the engine, one thread."""

    def __init__(self, eng, reqs: list[Request], t0: float, clock=time.perf_counter,
                 span: Callable[[str], Any] | None = None):
        self.eng, self.reqs, self.t0, self.clock = eng, reqs, t0, clock
        self.span = span or (lambda name: contextlib.nullcontext())
        self.next = 0
        self.active: list[Request] = []
        self.steps: list[Step] = []

    def submit(self, r: Request) -> None:
        r.submitted = self.clock()
        with self.span("inputs"):
            r.handle = self.eng.submit(
                r.prompt, on_token=lambda h, tok, r=r: r.deliver(tok, self.clock()))
        self.active.append(r)

    def run(self, until: float) -> None:
        """Serve until the host clock reads ``until``."""
        clock = self.clock
        while True:
            now = clock()
            if now >= until:
                return
            while self.next < len(self.reqs) and self.t0 + self.reqs[self.next].due <= now:
                self.submit(self.reqs[self.next])
                self.next += 1
            start = clock()
            with self.span("dispatch:step"):
                n = self.eng.step()
            context = []
            for r in self.active:
                if r.admit_step is None and r.handle.state.value != "queued":
                    r.admit_step = start
                if r.finished and not r.handle.done:
                    r.handle.cancel()
                if r.handle.state.value == "running":
                    context.append(len(r.prompt) + len(r.tokens))
            self.active = [r for r in self.active if not r.handle.done]
            if n:
                self.steps.append(Step(start, n, context))
            elif self.next < len(self.reqs):  # idle: wait for the next arrival
                time.sleep(max(0.0, min(until, self.t0 + self.reqs[self.next].due) - clock()))
            else:
                time.sleep(max(0.0, until - clock()))


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def model_config(config: dict):
    """The architecture of ``arch`` at the widths ``model`` states."""
    import dataclasses

    from repro.configs import get_config

    return dataclasses.replace(get_config(config["arch"]), **config["model"])


def make_weights(cfg, seed: int):
    """Weights of ``cfg``'s parameter tree, drawn on the device from the
    seed in one jitted call, in the type they are served in: every norm
    gain ``1 + 0.1 z``, every other array ``z / sqrt(fan-in)`` (its
    next-to-last axis), ``z`` standard normal."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import init_params

    shapes = jax.eval_shape(partial(init_params, cfg), jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        out = []
        for i, (path, s) in enumerate(paths):
            z = jax.random.normal(jax.random.fold_in(key, i), s.shape, jnp.float32)
            if "norm" in jax.tree_util.keystr(path):
                out.append((1.0 + 0.1 * z).astype(s.dtype))
            else:
                out.append((z * (1.0 / np.sqrt(s.shape[-2]))).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(gen)(harness.prng_key(seed, "weights")))


def make_engine(cfg, params, serve: dict):
    from repro.serve import ServeConfig, ServingEngine

    return ServingEngine(cfg, params, ServeConfig(**serve))


def warm_up(eng, traffic: dict, serve: dict) -> list[int]:
    """Serve one request per prefill bucket the traffic's prompts reach,
    cycling through them until every slot has held one, two tokens each;
    returns the buckets."""
    from repro.serve import ServeConfig, prefill_buckets

    lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    all_b = prefill_buckets(serve["max_len"], ServeConfig(**serve).min_bucket)
    first = next(i for i, b in enumerate(all_b) if b >= lo)
    last = next(i for i, b in enumerate(all_b) if b >= hi)
    buckets = list(all_b[first:last + 1])
    n = max(len(buckets), serve["batch_slots"])
    handles = [eng.submit(np.ones(buckets[k % len(buckets)], np.int32)) for k in range(n)]
    while not all(h.done for h in handles):
        eng.step()
        for h in handles:
            if len(h.tokens) >= 2 and not h.done:
                h.cancel()
    while eng.step():
        pass
    return buckets


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def release(eng, reqs: list[Request]) -> int:
    """Shut the engine down and drop every handle (each holds the engine,
    and so its cache and weights); returns the degradations it recorded."""
    eng.shutdown()
    for r in reqs:
        if r.handle is not None:
            r.state, r.handle = r.handle.state.value, None
    return len(eng.degradations)


def sample_checked(reqs: list[Request], k: int, seed: int) -> list[Request]:
    """``k`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in reqs if r.finished]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (len(done[i].tokens), len(done[i].prompt)))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, zlib.crc32(b"check")])
    pick = [longest] + [int(i) for i in rng.permutation(rest)[:k - 1]]
    return [done[i] for i in pick]


def _row_gaps(ref, choice):
    """Per position: how far the chosen token's reference logit lies below
    the reference's best, over the largest magnitude of those logits."""
    import jax.numpy as jnp

    best = ref.max(axis=-1)
    got = jnp.take_along_axis(ref, choice[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.maximum(jnp.abs(ref).max(axis=-1), 1e-30)


def readings(cell, params, checked: list[Request], variants: dict | None = None) -> dict:
    """The widest ``logit_gap`` of the served tokens over the checked
    requests and, for each variant (keyword arguments of the reference),
    the widest gap of the tokens that variant puts first at the same
    positions."""
    import jax
    import jax.numpy as jnp

    m, max_len = cell.config["model"], cell.config["serve"]["max_len"]
    gap_rows = jax.jit(_row_gaps)
    pick = jax.jit(lambda x: jnp.argmax(x, axis=-1).astype(jnp.int32))
    out = {"logit_gap": 0.0, **{v: 0.0 for v in variants or {}}}
    if not checked:
        return {k: float("inf") for k in out}
    with jax.default_matmul_precision("highest"):
        for r in checked:
            pl, n = len(r.prompt), len(r.tokens)
            seq = np.zeros(max_len, np.int32)
            seq[:pl], seq[pl:pl + n] = r.prompt, r.tokens
            nxt = np.zeros(max_len, np.int32)
            nxt[:-1] = seq[1:]
            rows = slice(pl - 1, pl - 1 + n)
            ref = cell.reference(params, seq, m)
            g = np.asarray(gap_rows(ref, jnp.asarray(nxt)))[rows]
            out["logit_gap"] = max(out["logit_gap"], float(g.max()))
            for name, kw in (variants or {}).items():
                kw = dict(kw)
                if kw.pop("shift_after_prompt", False):
                    kw["positions"] = np.arange(max_len) + (np.arange(max_len) >= pl)
                alt = cell.reference(params, seq, m, **kw)
                g = np.asarray(gap_rows(ref, pick(alt)))[rows]
                out[name] = max(out[name], float(g.max()))
                del alt
            del ref
    return out


def calibrate(cell, seeds: list[int], variant_seeds: list[int], seconds: float) -> dict:
    """Readings that the limits are set from, in one process: for each seed,
    a run's traffic (its lead-in and a window of ``seconds``, untraced), then
    the check's ``logit_gap`` of the served tokens and, for the seeds in
    ``variant_seeds``, the gap of the control and of each fault the
    configuration lists (``controls``, ``faults``: keyword arguments of the
    reference) at the same positions."""
    config, traffic = cell.config, cell.traffic
    cfg = model_config(config)
    variants = {**config["controls"], **config["faults"]}
    out = {}
    for seed in seeds:
        t = time.perf_counter()
        params = make_weights(cfg, seed)
        eng = make_engine(cfg, params, config["serve"])
        warm_up(eng, traffic, config["serve"])
        reqs = schedule(traffic, config["model"]["vocab"], seed, seconds, False)
        t0 = time.perf_counter()
        OpenLoop(eng, reqs, t0).run(t0 + float(traffic["lead_in_s"]) + seconds)
        release(eng, reqs)
        del eng
        gc.collect()
        checked = sample_checked(reqs, config["check"]["requests"], seed)
        out[seed] = dict(readings(cell, params, checked,
                                  variants if seed in variant_seeds else None),
                         failed_requests=sum(r.failed for r in reqs),
                         checked_tokens=[len(r.tokens) for r in checked],
                         checked_lengths=[len(r.prompt) + len(r.tokens) for r in checked])
        del params, reqs, checked
        gc.collect()
        log(f"calibrate seed {seed}: {json.dumps(out[seed])} "
            f"({time.perf_counter() - t:.1f} s)")
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             engine_hook: Callable | None = None) -> dict:
    """One run of a serving cell; returns the result object (the last line).
    ``engine_hook(engine, params)``, given, is applied to the engine before
    its warm-up (tests plant faults with it)."""
    dev = harness.device_info()
    counter = harness.CompileCounter()
    try:
        return _run(cell, seed, seconds, trace, t_start, engine_hook, dev, counter)
    finally:
        counter.close()


def _run(cell, seed, seconds, trace, t_start, engine_hook, dev, counter) -> dict:
    config, traffic = cell.config, cell.traffic
    m, serve = config["model"], config["serve"]
    cfg = model_config(config)
    params = make_weights(cfg, seed)
    eng = make_engine(cfg, params, serve)
    if engine_hook is not None:
        engine_hook(eng, params)
    t_warm = time.perf_counter()
    buckets = warm_up(eng, traffic, serve)
    warm_s = time.perf_counter() - t_warm
    reqs = schedule(traffic, m["vocab"], seed, seconds, trace)
    log(f"weights, engine and warm-up of buckets {buckets}: "
        f"{time.perf_counter() - t_start:.3f} s (warm-up {warm_s:.3f} s); "
        f"{len(reqs)} requests scheduled; device memory {harness.memory_stats()}")

    gc.collect()
    lead = float(traffic["lead_in_s"])
    t0 = time.perf_counter()
    loop = OpenLoop(eng, reqs, t0)
    loop.run(t0 + lead)
    w0 = t0 + lead
    setup_s = w0 - t_start
    counter.armed = True
    loop.run(w0 + seconds)
    counter.armed = False
    w1 = w0 + seconds
    in_window_compiles = counter.count
    log(f"window {seconds} s from {setup_s:.3f} s after start; compilations inside it: "
        f"{in_window_compiles}")

    trace_summary = None
    if trace:
        trace_summary = traced_round(loop, w1, float(traffic["trace_s"]))
    mem = harness.peak_bytes()
    degradations = release(eng, reqs)
    loop.eng = eng = None
    gc.collect()

    peak = harness.peaks_for(dev["kind"])
    record = window_record(reqs, t0, w0, w1, m, traffic)
    record.update(setup_s=setup_s, window_s=seconds, warm_up_s=warm_s, peak=peak,
                  in_window_compiles=in_window_compiles, trace=trace_summary,
                  occupancy_pct=occupancy(loop.steps, serve["batch_slots"], w0, w1))
    if trace_summary is not None:
        record["traced"] = traced_work(reqs, loop.steps, w1, w1 + float(traffic["trace_s"]),
                                       m, peak, trace_summary)
    del loop

    t_check = time.perf_counter()
    checked = sample_checked(reqs, config["check"]["requests"], seed)
    gap = readings(cell, params, checked)["logit_gap"]
    del params
    limits = config["limits"]
    checks = {"logit_gap": {"value": gap, "limit": float(limits["logit_gap"])},
              "failed_requests": {"value": sum(r.failed for r in reqs),
                                  "limit": limits["failed_requests"]},
              "degradations": {"value": degradations, "limit": limits["degradations"]}}
    log(f"check of {len(checked)} requests ({sum(len(r.tokens) for r in checked)} tokens): "
        f"{time.perf_counter() - t_check:.3f} s")

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for mt in wanted:
        v = cell.readers[mt["name"]](record)
        if v is not None:
            metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
    window_reqs = [r for r in reqs if r.phase == "window"]
    device = dict(dev, memory_peak_bytes=mem)
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
              "attempted": len(window_reqs), "failed": sum(r.failed for r in window_reqs),
              "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {"device_ops": trace_summary["device_ops"],
                               "idle_gaps": trace_summary["idle_gaps"]}
    result["checks"] = checks
    harness.write_table(cell.name, seed, trace, {"record": record, "result": result})
    return result


def window_record(reqs, t0, w0, w1, m, traffic) -> dict:
    """What the end-to-end and host-side metrics read: per request due in
    the window its time to first token (the elapsed time at the window's
    close if none came) and queue time; every gap between two tokens of a
    request whose later token came in the window; tokens delivered in it."""
    due_in = [r for r in reqs if r.phase == "window"]
    slo = traffic["slo"]
    ttft, tpot, queue, met = [], [], [], 0
    for r in due_in:
        due = t0 + r.due
        first = r.times[0] if r.times and r.times[0] < w1 else w1
        ttft.append((first - due) * 1e3)
        if r.admit_step is not None:
            queue.append(max(0.0, r.admit_step - due) * 1e3)
        per = None
        if len(r.times) > 1:
            per = (r.times[-1] - r.times[0]) / (len(r.times) - 1) * 1e3
            tpot.append(per)
        met += ttft[-1] <= slo["ttft_ms"] and (per is None or per <= slo["tpot_ms"])
    itl, tokens = [], 0
    for r in reqs:
        ts = r.times
        tokens += sum(w0 <= t < w1 for t in ts)
        itl += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if w0 <= b < w1]
    late = [(r.submitted - (t0 + r.due)) * 1e3 for r in due_in if r.submitted is not None]
    rec = {"requests_due": len(due_in),
           "requests_finished": sum(r.finished for r in due_in),
           "ttft_ms": ttft, "tpot_ms": tpot, "queue_ms": queue, "itl_ms": itl,
           "tokens_in_window": tokens, "slo_met_share": 100.0 * met / max(1, len(due_in)),
           "lateness_ms": {"p50": serve_counts.nearest_rank(late, 50),
                           "max": max(late) if late else None},
           "model": m}
    log(f"window: {len(due_in)} requests due, {rec['requests_finished']} finished, "
        f"{tokens} tokens; TTFT p50/p90 {serve_counts.nearest_rank(ttft, 50)}/"
        f"{serve_counts.nearest_rank(ttft, 90)} ms; ITL p50/p95 "
        f"{serve_counts.nearest_rank(itl, 50)}/{serve_counts.nearest_rank(itl, 95)} ms; "
        f"SLO met {rec['slo_met_share']:.1f}%; generator late p50/max "
        f"{rec['lateness_ms']['p50']}/{rec['lateness_ms']['max']} ms")
    return rec


def occupancy(steps: list[Step], slots: int, lo: float, hi: float) -> float | None:
    """Mean share of the slots that the steps dispatched in ``[lo, hi)`` ran."""
    inside = [s.occupied for s in steps if lo <= s.start < hi]
    return 100.0 * sum(inside) / slots / len(inside) if inside else None


def traced_work(reqs, steps, lo, hi, m, peak, summary) -> dict:
    """The traced round's work: model operations of the tokens delivered in
    ``[lo, hi)`` (a prompt's with its first token, which its prefill makes)
    and, for the decode steps dispatched in it, the mean least time of one
    (``serve_counts.decode_least_seconds``) beside the device time and count
    of the decode module's runs that the trace holds whole.  The decode
    module is the one whose runs come closest in number to the decode steps
    dispatched (one run each), the longer of two alike: a prefill program
    runs once per admission of its bucket, and the engine's small eager
    updates at most a few times per admission."""
    flops = 0
    for r in reqs:
        pl = len(r.prompt)
        for k, t in enumerate(r.times):
            if lo <= t < hi:
                flops += (serve_counts.prefill_flops(m, pl) if k == 0
                          else serve_counts.token_flops(m, pl + k - 1))
    least = [serve_counts.decode_least_seconds(m, s.context, peak)
             for s in steps if lo <= s.start < hi and s.context]
    runs = min(summary["modules"], key=lambda x: (abs(x[2] - len(least)), -x[1]), default=None)
    out = {"flops": flops, "decode_steps": len(least),
           "decode_least_s_mean": float(np.mean(least)) if least else None,
           "decode_module": runs}
    log(f"traced work: {flops:.4e} operations; {len(least)} decode steps, least "
        f"{out['decode_least_s_mean']} s each; decode module {out['decode_module']}")
    return out


def traced_round(loop: OpenLoop, start: float, length: float) -> dict:
    """The traced round: the schedule goes on for ``length`` seconds under
    the profiler; reduced to busy time, top device ops, idle gaps by host
    span and device time by module.  The trace is deleted once read."""
    import shutil

    import jax

    from . import trace_reduce

    tdir = harness.OUT_DIR / "trace"
    shutil.rmtree(tdir, ignore_errors=True)
    loop.span = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(str(tdir))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            loop.run(start + length)
    finally:
        jax.profiler.stop_trace()
    summary = trace_reduce.reduce_trace(
        trace_reduce.read_xplane(trace_reduce.find_xplane(str(tdir))))
    shutil.rmtree(tdir, ignore_errors=True)
    log(f"traced round: busy {summary['busy_s']:.6f} s of {summary['window_s']:.6f} s; "
        f"modules {json.dumps(summary['modules'][:8])}; ops, enclosed ones too, "
        f"{json.dumps(summary['nested_ops'])}")
    return summary
