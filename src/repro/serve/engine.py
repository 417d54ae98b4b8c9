"""Continuous-batching serving engine: request handles, batched decode,
pipelined dispatch, request-scoped fault isolation.

Static-shape design (TPU-friendly — no recompiles at runtime):

  * ``submit(prompt)`` returns a :class:`RequestHandle` (``.state``,
    ``.tokens``, ``.result()``, ``.cancel()``, optional per-token streaming
    callback); ``step()`` advances the engine one scheduling iteration and
    ``drain()`` runs to completion.  ``run()`` survives as a deprecated
    wrapper.
  * one jitted **batched decode** over all ``batch_slots`` at once
    (``models.model.decode_slots``): every slot carries its own cache-length
    scalar, so a freshly admitted request coexists with half-finished ones
    and a slot refill never retraces — the jit cache key is config content
    and the traced shapes depend only on ``(batch_slots, max_len)``.
  * **shape-bucketed prefill admission**: prompts are right-padded to a
    small set of power-of-two buckets, so arrivals hit a handful of cached
    prefill traces instead of one per distinct prompt length.  Padded cache
    rows are causally masked (the slot's ``len`` is reset to the true prompt
    length) and overwritten as decode proceeds, so bucketing is bit-exact.
    Families with token-recurrent state (hybrid / ssm) prefill at exact
    length — a padded token would pollute the carried SSM state.
  * **pipelined dispatch**: greedy sampling is fused into the jitted step
    (on-device argmax feeding the next step's tokens), so step N+1 is
    dispatched while step N's tokens are still in flight; the host blocks
    only at harvest points, ``pipeline_depth`` steps behind the dispatch
    frontier.  Temperature sampling needs the logits on the host each step
    and therefore harvests synchronously.
  * **spans** (``repro.core.spans``): ``serve.prefill`` around each
    admission's prefill and its first token, ``serve.decode`` around each
    batched step's dispatch.  Each carries the step's expert counters (the
    new state's ``counts``, zero without experts), read at the sync the
    engine already makes (the prefill's logits, the step's harvest):
    ``pairs`` (token, held expert) pairs computed and ``experts`` held
    experts that received a token, summed over layers.  A decode span also carries ``lens``, the
    cache length of each slot it ran, which the host tracks.

Failure model (request-scoped — one bad request never kills the batch):

  * a request whose prefill or harvest raises, or whose logits go
    non-finite (checked at prefill for every family and per slot on the
    synchronous sampling path), transitions to ``FAILED`` with the captured
    error; its slot is recycled and the surviving slots keep decoding
    token-for-token as if the failed request had hit eos.
  * ``submit(..., timeout_s=)`` arms a per-request deadline: overdue
    requests transition to ``TIMED_OUT`` at the next harvest (or while
    still queued), freeing their slot.
  * ``handle.cancel()`` withdraws a queued request or recycles a running
    one (``CANCELLED``); in-flight overshoot tokens are dropped at harvest.
  * a failure of the whole batched step fails the requests that occupied
    slots at dispatch time, but the engine itself stays serviceable.
  * ``compile_resilient`` is the hot-swap guardrail for tuned kernels: a
    candidate program is compiled *and validated* under each backend in
    order (``pallas`` → ``xla`` by default, via ``Daisy``'s backend
    degradation), so a broken Pallas build degrades to the XLA lowering
    instead of surfacing mid-traffic; degradations are recorded on
    ``engine.degradations``.

Deterministic fault injection (tests + ``bench_resilience``): pass a seeded
``fault.FaultPlan``; sites ``serve.prefill`` / ``serve.decode`` /
``serve.logits`` / ``serve.step`` poison exactly the scheduled requests.

Minimal serving loop::

    from repro.serve import ServeConfig, ServingEngine

    eng = ServingEngine(cfg, params, ServeConfig(batch_slots=8, max_len=512))
    h = eng.submit(prompt_tokens, on_token=lambda h, t: print(h.rid, t))
    while not h.done:          # or h.result() to block for this request,
        eng.step()             # or eng.drain() to run everything
    print(h.tokens)

Request states: ``QUEUED -> RUNNING -> {DONE, FAILED, TIMED_OUT,
CANCELLED}``; terminal handles expose ``.error`` and re-raise it from
``.result()``.  See ``docs/architecture.md`` (Deployment layers) for the
surrounding system and ``repro.autotune`` for the tuner hook.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from concurrent.futures import CancelledError
from dataclasses import dataclass, field
from enum import Enum
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core import spans
from ..core.database import TuningDatabase
from ..fault import FaultInjected, FaultPlan
from ..models import model as M


class NonFiniteLogits(RuntimeError):
    """A request's logits went NaN/inf — numeric poison isolated to the one
    request instead of propagating through the batch."""


class RequestState(Enum):
    QUEUED = "queued"
    RUNNING = "running"
    COMPLETED = "completed"
    FAILED = "failed"
    TIMED_OUT = "timed_out"
    CANCELLED = "cancelled"


TERMINAL_STATES = frozenset(
    {RequestState.COMPLETED, RequestState.FAILED, RequestState.TIMED_OUT,
     RequestState.CANCELLED}
)


@dataclass
class ServeConfig:
    batch_slots: int = 4
    max_len: int = 512
    max_new_tokens: int = 32
    temperature: float = 0.0
    seed: int = 0
    eos_id: int = -1  # -1: never stops early
    # dispatch-ahead distance for the greedy path: how many batched steps may
    # be in flight before the host blocks on the oldest one's tokens
    pipeline_depth: int = 2
    min_bucket: int = 16  # smallest prefill bucket (powers of two upward)


def prefill_buckets(max_len: int, min_bucket: int = 16) -> tuple[int, ...]:
    """The padded prompt lengths prefill admission rounds up to: powers of
    two from ``min_bucket`` to ``max_len`` (``max_len`` itself always
    included so any prompt the cache can hold has a bucket)."""
    out: list[int] = []
    b = min_bucket
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


@dataclass(eq=False)
class RequestHandle:
    """A submitted request's live view: ``tokens`` grows as the engine
    harvests decode steps, ``state`` walks QUEUED → RUNNING → one terminal
    state (COMPLETED / FAILED / TIMED_OUT / CANCELLED), and ``result()``
    drives the engine until completion.  An ``on_token`` callback
    (``fn(handle, token)``) streams tokens as they are harvested; ``error``
    holds the captured exception of a FAILED request."""

    rid: int
    prompt: np.ndarray
    tokens: list[int] = field(default_factory=list)
    state: RequestState = RequestState.QUEUED
    error: BaseException | None = None
    deadline: float | None = None  # absolute time.monotonic() cutoff
    on_token: Callable[["RequestHandle", int], None] | None = None
    _engine: "ServingEngine | None" = field(default=None, repr=False)

    @property
    def done(self) -> bool:
        """True once the request reached any terminal state."""
        return self.state in TERMINAL_STATES

    @property
    def failed(self) -> bool:
        return self.state is RequestState.FAILED

    def result(self) -> list[int]:
        """Block until this request completes (drives the owning engine's
        ``step()`` loop) and return the generated tokens.  Raises the
        captured error for a FAILED request, :class:`TimeoutError` for a
        TIMED_OUT one and :class:`CancelledError` after ``cancel()``."""
        while not self.done:
            if self._engine is None or self._engine.step() == 0 and not self.done:
                raise RuntimeError(f"request {self.rid} cannot complete: "
                                   "engine is idle")
        if self.state is RequestState.FAILED:
            raise self.error if self.error is not None else \
                RuntimeError(f"request {self.rid} failed")
        if self.state is RequestState.TIMED_OUT:
            raise TimeoutError(
                f"request {self.rid} exceeded its deadline after "
                f"{len(self.tokens)} token(s)")
        if self.state is RequestState.CANCELLED:
            raise CancelledError(f"request {self.rid} was cancelled")
        return self.tokens

    def cancel(self) -> bool:
        """Withdraw the request: True if it transitioned to CANCELLED,
        False if it had already reached a terminal state."""
        if self.done:
            return False
        if self._engine is not None:
            self._engine._cancel(self)
        else:
            self.state = RequestState.CANCELLED
        return True

    def _overdue(self, now: float) -> bool:
        return self.deadline is not None and now > self.deadline

    # -- engine-side bookkeeping ------------------------------------------
    def _append(self, tok: int, scfg: ServeConfig) -> None:
        self.tokens.append(tok)
        if self.on_token is not None:
            self.on_token(self, tok)
        if len(self.tokens) >= scfg.max_new_tokens or tok == scfg.eos_id:
            self.state = RequestState.COMPLETED


class ServingEngine:
    """Single-host continuous-batching engine; under pjit the same step
    functions shard over the mesh (batch -> data axis, heads/experts ->
    model axis).

    Lifecycle::

        eng = ServingEngine(cfg, params, ServeConfig(...))
        h = eng.submit(prompt, timeout_s=5.0)  # -> RequestHandle, queued
        eng.step()                      # admit + one batched decode + harvest
        eng.drain()                     # run to completion, {rid: tokens}
        h.result()                      # or drive until this handle is done
        h.cancel()                      # withdraw a queued/running request

    ``drain()`` (and ``shutdown()``) closes the engine: later ``submit``
    calls raise instead of silently corrupting slot bookkeeping.
    """

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 tuning_db: TuningDatabase | None = None, mesh=None,
                 fault_plan: FaultPlan | None = None,
                 logit_program=None, logit_inputs=None,
                 tuner=None, program_backend: str = "xla"):
        """``mesh`` (any mesh with a ``model`` axis, e.g. from
        ``launch.mesh.make_mesh``) places the parameters with the sharding
        planner's specs before the first jit — the decode steps then
        partition across the mesh via the committed shardings instead of
        running single-device.  ``fault_plan`` arms deterministic fault
        injection (tests / resilience benchmark).

        ``logit_program`` (a canonical loop-nest :class:`~repro.core.ir.
        Program`, e.g. ``repro.autotune.logit_pipeline_program``) fuses a
        tuned logit post-processing stage into the jitted decode step: the
        batched decode's ``(N, V)`` logits enter the program's ``X`` input
        vocab-major as ``(V, N)`` and sampling reads its ``Y`` output.  The
        program is lowered through ``Daisy`` under ``program_backend``
        against ``tuning_db``, and the composite's jit-cache key carries
        ``(db.uid, db.generation)`` — a database commit hot-swaps the step
        fn at the next ``step()`` with zero traffic interruption.
        ``logit_inputs`` supplies the program's deployment operand arrays
        (missing ones are zero-filled).  ``tuner`` attaches a
        ``repro.autotune.SearchSupervisor``: the engine observes per-step
        telemetry into ``tuner.telemetry``, registers ``logit_program``,
        and drives ``tuner.maybe_launch()`` / ``tuner.poll()`` every
        ``tuner.check_every`` steps — the full online-adaptation loop.
        """
        from ..models.lowering import deployment_context

        self.cfg, self.scfg = cfg, scfg
        if tuner is not None:
            if tuning_db is None:
                tuning_db = tuner.db
            elif tuning_db is not tuner.db:
                raise ValueError(
                    "tuner.db and tuning_db are different databases; the "
                    "supervisor must commit swaps into the database the "
                    "engine resolves recipes from")
        # Shared deployment boilerplate (mesh placement + warm pretuned
        # tuning DB + fingerprint-keyed jit lookups) — same helper the
        # Trainer constructor uses.
        self._ctx = deployment_context(
            cfg, params, mesh=mesh, tuning_db=tuning_db,
            telemetry=tuner.telemetry if tuner is not None else None)
        self.mesh = mesh
        self.params = self._ctx.params
        self.tuning_db = self._ctx.tuning_db
        self.telemetry = self._ctx.telemetry
        self.fault_plan = fault_plan
        self.tuner = tuner
        self._step_count = 0
        # prefill (s >= 1) and slot-batched decode steps; content-keyed so
        # re-created engines with an equal config share the functions and
        # their jax trace caches — slot refills and restarts never retrace
        self._decode = self._ctx.jitted(
            "serve.prefill", lambda: jax.jit(partial(M.prefill, cfg)))
        self._step_greedy = self._ctx.jitted(
            "serve.decode_slots_greedy",
            lambda: jax.jit(partial(M.decode_slots_greedy, cfg)))
        self._step_logits = self._ctx.jitted(
            "serve.decode_slots", lambda: jax.jit(partial(M.decode_slots, cfg)))
        self.logit_program = logit_program
        if logit_program is not None:
            from ..core import Daisy, program_fingerprint

            self._daisy = Daisy(db=self.tuning_db, backend=program_backend)
            self._prog_key = program_fingerprint(logit_program)
            self._prog_aux = self._build_aux(logit_inputs or {})
            self._telemetry_key = self._prog_key
            if tuner is not None:
                tuner.register(logit_program)
            self._prog_gen: int | None = None
            self._resolve_step_fns()
        else:
            from ..core.cache import fingerprint_obj

            self._telemetry_key = f"serve.step:{fingerprint_obj(cfg)[:12]}"
            self._dispatch_greedy = self._step_greedy
            self._dispatch_logits = self._step_logits

        n = scfg.batch_slots
        self._buckets = prefill_buckets(scfg.max_len, scfg.min_bucket)
        self._states = M.init_slot_states(cfg, n, scfg.max_len)
        self._tokens = jnp.zeros((n,), jnp.int32)  # last sampled, per slot
        self._slots: list[RequestHandle | None] = [None] * n
        self._lens = [0] * n  # each slot's cache length, as the host tracks it
        self._queue: deque[RequestHandle] = deque()
        # in-flight dispatched steps: (device tokens (N,) or logits, device
        # expert counts (2,), {slot: handle}, the step's span)
        self._pending: deque[tuple[Any, Any, dict[int, RequestHandle], Any]] = deque()
        self.results: dict[int, list[int]] = {}
        self.failed: dict[int, RequestHandle] = {}
        # (program-name, from-backend, to-backend) of every compile that
        # degraded down the backend chain (see compile_resilient)
        self.degradations: list[tuple[str, str, str]] = []
        self._inflight: dict[int, RequestHandle] = {}
        self._closed = False
        self._next_rid = 0
        self.rng = np.random.default_rng(scfg.seed)

    # -- public API ------------------------------------------------------------
    def submit(self, prompt, _legacy_prompt=None, *, rid: int | None = None,
               on_token: Callable[[RequestHandle, int], None] | None = None,
               timeout_s: float | None = None,
               ) -> RequestHandle:
        """Queue a prompt; returns its :class:`RequestHandle`.

        ``timeout_s`` arms a per-request deadline (measured from submission):
        an overdue request transitions to TIMED_OUT at the next harvest and
        frees its slot.  Duplicate in-flight ``rid``s and submissions after
        ``drain()``/``shutdown()`` are rejected.

        The legacy positional form ``submit(rid, prompt)`` still works but
        is deprecated — pass the prompt first (an explicit id via ``rid=``).
        """
        if _legacy_prompt is not None:
            warnings.warn(
                "ServingEngine.submit(rid, prompt) is deprecated; use "
                "submit(prompt, rid=...) -> RequestHandle",
                DeprecationWarning, stacklevel=2)
            rid, prompt = int(prompt), _legacy_prompt
        if self._closed:
            raise RuntimeError(
                "ServingEngine is shut down (drain()/shutdown() was called); "
                "create a new engine to serve more requests")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(f"prompt must be a non-empty 1-D token array, "
                             f"got shape {prompt.shape}")
        if prompt.size > self._buckets[-1]:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the largest prefill "
                f"bucket {self._buckets[-1]} (max_len={self.scfg.max_len})")
        if prompt.size + self.scfg.max_new_tokens > self.scfg.max_len:
            raise ValueError(
                f"prompt length {prompt.size} + max_new_tokens "
                f"{self.scfg.max_new_tokens} exceeds max_len "
                f"{self.scfg.max_len} (the decode cache would overflow)")
        if rid is None:
            rid = self._next_rid
        elif rid in self._inflight:
            raise ValueError(
                f"rid {rid} is already in flight (state "
                f"{self._inflight[rid].state.value}); duplicate ids would "
                "corrupt slot bookkeeping — pass a fresh rid or omit it")
        self._next_rid = max(self._next_rid, rid) + 1
        deadline = time.monotonic() + timeout_s if timeout_s is not None else None
        h = RequestHandle(rid=rid, prompt=prompt, on_token=on_token,
                          deadline=deadline, _engine=self)
        self._inflight[rid] = h
        self._queue.append(h)
        return h

    def step(self) -> int:
        """One scheduling iteration: harvest the mature in-flight step,
        admit queued requests into free slots, dispatch one batched decode
        over the occupied slots.  Returns the number of occupied slots
        after dispatch (0 = idle: queue empty, nothing in flight).

        Instrumented for online tuning: busy steps are timed into the
        telemetry sink (a no-op predicate when disabled), and an attached
        tuner is driven every ``tuner.check_every`` steps — launches
        background searches on the hottest nests and applies/rolls back
        swaps at the poll point."""
        if self.logit_program is not None:
            self._resolve_step_fns()  # picks up database commits (hot swap)
        t0 = time.perf_counter()
        n = self._step_impl()
        if n:
            self.telemetry.observe(self._telemetry_key,
                                   time.perf_counter() - t0)
        self._step_count += 1
        if self.tuner is not None \
                and self._step_count % self.tuner.check_every == 0:
            self.tuner.maybe_launch()
            self.tuner.poll(engine=self)
        return n

    def _step_impl(self) -> int:
        scfg = self.scfg
        sync = scfg.temperature > 0.0
        depth = 0 if sync else max(0, scfg.pipeline_depth)
        self._expire_queued()
        self._admit()
        live = {i: h for i, h in enumerate(self._slots) if h is not None}
        if not live:
            while self._pending:
                self._harvest_one()
            return 0
        try:
            if self.fault_plan is not None:
                self.fault_plan.maybe_raise("serve.step")
            with spans.span("serve.decode", slots=len(live)) as sp:
                sp.attrs["lens"] = [self._lens[i] for i in live]
                if sync:
                    out, self._states = self._dispatch_logits(
                        self.params, self._states, self._tokens)
                else:
                    # pipelined: the sampled tokens stay on device and feed
                    # the next dispatch; the host looks at them
                    # `pipeline_depth` steps later
                    out, self._states = self._dispatch_greedy(
                        self.params, self._states, self._tokens)
                    self._tokens = out
            self._pending.append((out, self._states["counts"], live, sp))
            for i in live:
                self._lens[i] += 1
        except Exception as e:  # noqa: BLE001 — batch-level dispatch failure
            # the whole dispatched step is lost: fail the requests that
            # occupied slots, recycle them, and keep the engine serviceable
            # for the queue and for future submissions
            for i, h in live.items():
                self._fail(h, e, slot=i)
            return self._step_impl() if self._queue or self._pending else 0
        # block on overdue steps: at most `depth` stay in flight (0 = the
        # host sees every step's result before dispatching the next)
        while len(self._pending) > depth:
            self._harvest_one()
        return len(live)

    def drain(self) -> dict[int, list[int]]:
        """Run until the queue and every slot are empty, then shut the
        engine down; returns ``rid -> generated tokens`` for every request
        that COMPLETED (failed / timed-out / cancelled requests carry their
        outcome on their handle)."""
        while self._queue or self._pending or any(
                h is not None for h in self._slots):
            self.step()
        self._closed = True
        return self.results

    def shutdown(self) -> None:
        """Close the engine without draining: queued requests are cancelled,
        running ones keep their partial tokens and transition to CANCELLED;
        later ``submit`` calls raise."""
        for h in list(self._queue) + [h for h in self._slots if h is not None]:
            if not h.done:
                self._cancel(h)
        while self._pending:  # sync the device so nothing dangles
            self._harvest_one()
        self._closed = True

    def run(self) -> dict[int, list[int]]:
        """Deprecated: drain the queue; returns rid -> generated tokens.

        Migration: ``submit(prompt)`` now returns a :class:`RequestHandle`
        — poll ``handle.done`` / read ``handle.tokens`` while calling
        ``engine.step()``, call ``handle.result()`` to block for one
        request, or ``engine.drain()`` for the old run-to-completion
        behaviour (same return value as ``run()``).
        """
        warnings.warn(
            "ServingEngine.run() is deprecated; use submit()/step()/drain() "
            "or RequestHandle.result()", DeprecationWarning, stacklevel=2)
        return self.drain()

    def compile_resilient(self, program,
                          backends: tuple[str, ...] = ("pallas", "xla")):
        """Hot-swap guardrail: compile (and validate) a tuned canonical
        program for this engine, degrading across ``backends`` in order.

        A background ``evolve_recipe`` winner must never be swapped into a
        live engine on the strength of a compile that hasn't run: each rung
        builds through ``Daisy`` (whose recipe degradation maps Pallas kinds
        onto XLA equivalents under ``'xla'``) and executes once on random
        inputs before being accepted.  Falls through to the next backend on
        any failure; degradations are recorded on ``self.degradations``.
        Returns a :class:`repro.fault.DegradedCompile`.
        """
        from ..fault import compile_with_degradation

        res = compile_with_degradation(
            program, backends=backends, db=self.tuning_db,
            fault_plan=self.fault_plan)
        for b, _err in res.errors:
            self.degradations.append(
                (getattr(program, "name", "?"), b, res.backend))
        return res

    def explain_kernels(self) -> str:
        """Pass-pipeline + contraction-plan report for this engine's config
        at its serving shape (ops introspection; content-cached so repeated
        calls and re-created engines share one pipeline run)."""
        from ..models.lowering import kernel_report

        return self._ctx.jitted(
            "serve.kernel_report",
            lambda: kernel_report(
                self.cfg, seq=self.scfg.max_len, batch=self.scfg.batch_slots,
                db=self.tuning_db,
            ),
            self.scfg.max_len, self.scfg.batch_slots,
            self.tuning_db.uid, self.tuning_db.generation,
        )

    # -- tuned logit-program composite -----------------------------------------
    def _build_aux(self, given: dict) -> dict:
        """Validate + stage the logit program's deployment operands.

        The engine owns ``X`` (the step's vocab-major logits) and reads
        ``Y``; every other input array of the *normalized* program is a
        deployment operand — taken from ``logit_inputs`` when given
        (shape-checked), zero-filled otherwise.  Unknown names are errors:
        a typo'd operand silently zero-filled would corrupt served tokens.
        """
        prog = self._daisy._normalized(self.logit_program)
        shapes = {a.name: tuple(a.shape) for a in prog.input_arrays}
        v, n = self.cfg.vocab, self.scfg.batch_slots
        for io in ("X", "Y"):
            if shapes.get(io) != (v, n):
                raise ValueError(
                    f"logit program must carry {io} of shape (vocab, "
                    f"batch_slots) = ({v}, {n}), got "
                    f"{shapes.get(io)} in {self.logit_program.name!r}")
        unknown = sorted(set(given) - set(shapes))
        if unknown:
            raise ValueError(
                f"logit_inputs name(s) {unknown} are not input arrays of "
                f"{self.logit_program.name!r} (has {sorted(shapes)})")
        aux: dict[str, jnp.ndarray] = {}
        for name, shape in shapes.items():
            if name == "X":
                continue
            if name in given:
                arr = jnp.asarray(given[name], jnp.float32)
                if tuple(arr.shape) != shape:
                    raise ValueError(
                        f"logit_inputs[{name!r}] has shape {tuple(arr.shape)}"
                        f", program expects {shape}")
            else:
                arr = jnp.zeros(shape, jnp.float32)
            aux[name] = arr
        return aux

    def _resolve_step_fns(self) -> None:
        """(Re)build the decode+logit-program composites when the tuning
        database has moved — this IS the hot swap: the jit-cache key carries
        ``(program fingerprint, db.uid, db.generation)``, so a supervisor
        commit (or rollback) resolves a fresh composite on the next step
        while older generations stay cached (rollback is a cache hit)."""
        gen = self.tuning_db.generation
        if gen == self._prog_gen:
            return
        self._prog_gen = gen
        cfg, daisy = self.cfg, self._daisy
        prog, aux = self.logit_program, self._prog_aux

        def composite(sample_greedy: bool):
            # raw (unjitted) program fn: composes under the outer jit, and
            # Daisy's compile cache (keyed on db state) does the recipe work
            pfn, _plan = daisy.compile(prog, jit=False)

            def stepfn(params, states, tokens):
                logits, states = M.decode_slots(cfg, params, states, tokens)
                env = dict(aux)
                env["X"] = logits.T  # (N, V) -> vocab-major (V, N)
                out = pfn(env)["Y"]
                if sample_greedy:
                    return jnp.argmax(out, axis=0).astype(jnp.int32), states
                return out.T, states  # back to (N, V) for host sampling

            return jax.jit(stepfn)

        self._dispatch_greedy = self._ctx.jitted(
            "serve.decode_slots_greedy+program", lambda: composite(True),
            self._prog_key, self.tuning_db.uid, gen)
        self._dispatch_logits = self._ctx.jitted(
            "serve.decode_slots+program", lambda: composite(False),
            self._prog_key, self.tuning_db.uid, gen)

    # -- internals -------------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        # token-recurrent families can't mask a padded prompt token out of
        # the carried state, so they prefill at exact length (still one
        # cached trace per *distinct* length — the pre-bucketing behaviour)
        if self.cfg.family in ("hybrid", "ssm"):
            return n
        return next(b for b in self._buckets if b >= n)

    def _prefill(self, h: RequestHandle):
        """Bucket-padded prefill of one request into a fresh b=1 state;
        returns (last-valid-position logits (V,), state)."""
        cfg, scfg = self.cfg, self.scfg
        s = int(h.prompt.size)
        bucket = self._bucket_for(s)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :s] = h.prompt
        state = M.init_decode_state(cfg, 1, scfg.max_len, ring=False)
        if cfg.family == "audio":
            # stub frontend: encoder memory from pseudo frame embeddings
            emb = jnp.zeros((1, cfg.frontend_len, cfg.d_model), M._dtype(cfg))
            state["memory"] = M.encode(cfg, self.params, emb)
        # the state advances by the true length: padded cache rows beyond it
        # are causally masked and get overwritten as decode proceeds
        logits, state = self._decode(self.params, state, jnp.asarray(toks),
                                     jnp.asarray(s, jnp.int32))
        return logits[0], state

    def _sample_from(self, lf: np.ndarray) -> int:
        if self.scfg.temperature <= 0.0:
            return int(lf.argmax())
        p = np.exp((lf - lf.max()) / self.scfg.temperature)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    def _check_finite(self, lf: np.ndarray, h: RequestHandle) -> None:
        if not np.isfinite(lf).all():
            raise NonFiniteLogits(
                f"request {h.rid}: non-finite logits "
                f"(nan={int(np.isnan(lf).sum())}, inf={int(np.isinf(lf).sum())} "
                f"of {lf.size})")

    # -- terminal transitions -------------------------------------------------
    def _retire(self, h: RequestHandle, slot: int | None = None) -> None:
        self._inflight.pop(h.rid, None)
        if slot is not None and self._slots[slot] is h:
            self._slots[slot] = None

    def _finish(self, h: RequestHandle, slot: int | None = None) -> None:
        self.results[h.rid] = h.tokens
        self._retire(h, slot)

    def _fail(self, h: RequestHandle, err: BaseException,
              slot: int | None = None) -> None:
        h.state = RequestState.FAILED
        h.error = err
        self.failed[h.rid] = h
        self._retire(h, slot)

    def _timeout(self, h: RequestHandle, slot: int | None = None) -> None:
        h.state = RequestState.TIMED_OUT
        self._retire(h, slot)

    def _cancel(self, h: RequestHandle) -> None:
        h.state = RequestState.CANCELLED
        try:
            self._queue.remove(h)
        except ValueError:
            pass
        slot = next((i for i, s in enumerate(self._slots) if s is h), None)
        self._retire(h, slot)

    def _expire_queued(self) -> None:
        """TIMED_OUT sweep over requests still waiting for a slot (their
        deadline can pass while every slot is busy)."""
        now = time.monotonic()
        for h in [h for h in self._queue if h._overdue(now)]:
            self._queue.remove(h)
            self._timeout(h)

    def _admit(self) -> None:
        """Fill free slots from the queue: bucketed prefill, sample the
        first token, write the slot state.  A request whose prefill raises
        or whose prefill logits are non-finite fails alone — admission
        continues with the rest of the queue."""
        while self._queue and None in self._slots:
            h = self._queue.popleft()
            if h._overdue(time.monotonic()):
                self._timeout(h)
                continue
            try:
                fault = None if self.fault_plan is None else \
                    self.fault_plan.maybe_raise("serve.prefill", key=h.rid)
                with spans.span("serve.prefill", tokens=int(h.prompt.size)) as sp:
                    last_logits, state = self._prefill(h)
                    lf = np.asarray(last_logits, np.float32)
                    self._count(sp, state["counts"])
                if fault is not None and fault.kind == "nan":
                    lf = np.full_like(lf, np.nan)
                self._check_finite(lf, h)
                t0 = self._sample_from(lf)
                h.state = RequestState.RUNNING
                h._append(t0, self.scfg)
            except Exception as e:  # noqa: BLE001 — request-scoped isolation
                self._fail(h, e)
                continue
            if h.done:  # eos / max_new_tokens == 1: never occupies a slot
                self._finish(h)
                continue
            i = self._slots.index(None)
            self._slots[i] = h
            self._lens[i] = int(h.prompt.size)
            self._states = M.write_slot(self._states, i, state)
            self._tokens = self._tokens.at[i].set(t0)

    @staticmethod
    def _count(sp: spans.Span, counts) -> None:
        """Put a step's expert counters, already on the host's side of a
        sync, on its span."""
        pairs, experts = (int(c) for c in np.asarray(counts))
        sp.attrs.update(pairs=pairs, experts=experts)

    def _harvest_one(self) -> None:
        """Materialize the oldest in-flight step's tokens and credit them to
        the handles that occupied each slot at dispatch time.  This is the
        only point the host blocks on the device — and the point where
        per-request outcomes are decided: deadlines expire here, injected or
        raised per-request work fails here, and a failed/overdue request
        frees its slot while every other slot's tokens are credited
        untouched."""
        out, counts, live, sp = self._pending.popleft()
        arr = np.asarray(out)  # blocks until this step's results are ready
        self._count(sp, counts)
        now = time.monotonic()
        for i, h in live.items():
            if h.done:  # finished in a younger harvest; overshoot dropped
                continue
            if h._overdue(now):
                self._timeout(h, slot=i)
                continue
            try:
                fault = None if self.fault_plan is None else \
                    self.fault_plan.maybe_raise("serve.decode", key=h.rid)
                if arr.ndim == 1:  # greedy path: sampled tokens (N,)
                    tok = int(arr[i])
                else:  # sync path: logits (N, V), sample on host
                    lf = arr[i]
                    lfault = self.fault_plan.maybe_raise(
                        "serve.logits", key=h.rid) if self.fault_plan else None
                    if (fault is not None and fault.kind == "nan") or (
                            lfault is not None and lfault.kind == "nan"):
                        lf = np.full_like(lf, np.nan)
                    self._check_finite(lf, h)
                    tok = self._sample_from(lf)
                    self._tokens = self._tokens.at[i].set(tok)
                h._append(tok, self.scfg)
            except Exception as e:  # noqa: BLE001 — request-scoped isolation
                self._fail(h, e, slot=i)
                continue
            if h.done:
                self._finish(h, slot=i)
