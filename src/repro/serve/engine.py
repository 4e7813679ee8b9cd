"""Step-interleaved continuous-batching serving engine.

The engine drains a :class:`~repro.serve.request.RequestQueue` through the
executor's **resumable stepping API** (``start_run`` / ``advance_run`` for
static plans — one :class:`~repro.core.plan.ExecutionPlan` segment per
advance — and, for adaptive entries, ``start_adaptive_fused_run`` /
``advance_adaptive_fused`` when the executor supports the fused path: a
whole ``adaptive_chunk`` of steps in ONE donated program dispatch, with
the reuse decisions made on device, so timeslicing adaptive runs costs
zero per-step host round-trips.  Non-scannable solvers fall back to the
host-dispatched ``start_adaptive_run`` / ``advance_adaptive_run`` loop —
one decision sync + program dispatch per step).  Several in-flight
micro-batches timeslice the device: which one advances each tick is
decided by a pluggable :class:`repro.slo.SchedulingPolicy` — the default
``interleave`` (round-robin, so a short, heavily-cached schedule admitted
behind a full-compute one finishes early instead of convoying behind it),
``fcfs`` (the convoy baseline), ``edf`` (least-slack-first over member
deadlines, remaining-steps-aware), or an ``elastic`` policy object that
additionally drives the store's τ ladders from measured p95 waits.
Preemption granularity is the advance unit (plan segment / adaptive
chunk) — a batch is never torn mid-program.

SLO semantics (all optional — without them the engine behaves exactly as
before): requests may carry a :class:`repro.slo.SLO`; each tick first
runs an SLO sweep that sheds quality-infeasible requests (no registered
rung at or below the request's ``max_tau``) and, when an
:class:`repro.slo.AdmissionController` is installed, sheds/defers against
the estimated backlog (queue depth × the online-calibrated per-step
service cost).  Every rejection is recorded with a reason in
``ServeEngine.shed`` and the metrics — check :meth:`ServeEngine.outcome`
for any rid.

Determinism contract: a micro-batch over requests ``[r0..rn-1]`` samples
with ``batch_key(seeds)`` — serving a batch is *bit-identical* to calling
``DiffusionPipeline.generate(params, batch_key(seeds), n, label=...)``
with the same store entry, because start+advance-until-done executes
exactly the ops of ``sample_with_plan`` / ``sample_adaptive``
(``tests/test_serve.py`` asserts this end-to-end).

Compiled-program budget: programs specialize on (signature, batch shape),
so the engine's compile count is bounded by |buckets used| ×
|signature pool| across all entries — reported by :meth:`ServeEngine.report`
against the executor's ``xla_program_count``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import diffusion
from repro.obs import (NULL_TRACER, MetricsRegistry, profiling,
                       run_cache_reports)
from repro.resilience.faults import NAN_LATENT, STUCK_BATCH, BatchFault
from repro.serve.batcher import MicroBatch, MicroBatcher, bucket_sizes
from repro.serve.metrics import ServerMetrics
from repro.serve.request import Request, RequestQueue, WallClock
from repro.serve.store import ArtifactStore

#: built-in scheduler names (resolved through repro.slo.resolve_policy;
#: "elastic" additionally exists as a policy *object* since it needs a
#: constructed controller)
SCHEDULERS = ("interleave", "fcfs", "edf")


def batch_key(seeds: Sequence[int]):
    """Deterministic PRNG key of a micro-batch: a fold of the member
    requests' seeds (order-sensitive — the batch row order).  Exposed so
    tests and clients can replay any served batch through
    ``DiffusionPipeline.generate`` and get bit-identical latents."""
    key = jax.random.PRNGKey(len(seeds))
    for s in seeds:
        # full 32-bit fold: seeds differing only in bit 31 must not
        # collapse to the same key
        key = jax.random.fold_in(key, jnp.uint32(int(s) & 0xFFFFFFFF))
    return key


@dataclasses.dataclass
class BatchRecord:
    """Provenance of one served micro-batch (enough to replay it)."""
    group: str
    version: int
    bucket: int
    rids: Tuple[int, ...]
    seeds: Tuple[int, ...]
    labels: Tuple[Optional[int], ...]
    num_steps: int
    compute_fraction: float
    formed_at: float
    finished_at: float
    decisions: Optional[Tuple[tuple, ...]] = None   # adaptive runs only
    tau: float = 0.0                          # realized τ (rung at launch)
    quality_cost: Optional[float] = None      # predicted, from proxy map
    #: continuous-batching provenance: every join / regroup / coalesce /
    #: split-retry event this batch's run-state went through, in order
    #: (``join@<step>:<rids>``, ``regroup@<step>:<rids>``, …).  Empty for
    #: a batch that rode formation → finish unchanged; with per-row keys
    #: replay stays per-request (``generate(params, batch_key([seed]),
    #: 1)``) no matter the lineage.
    lineage: Tuple[str, ...] = ()


class _EagerState:
    """Run-state stand-in for the ``--eager`` escape hatch (whole batch
    sampled in one advance; no interleaving); it holds the batch's
    conditioning, which the other run kinds carry in their run state."""

    def __init__(self, cond: Dict):
        self.cond = cond
        self.x = None
        self.decisions = None

    @property
    def done(self) -> bool:
        return self.x is not None


@dataclasses.dataclass
class _Inflight:
    mb: MicroBatch
    kind: str                                 # "plan" | "adaptive" | "eager"
    rs: object
    #: per-row health known so far (np bool, True = healthy); None = all
    #: healthy.  Monotone: a poisoned row never recovers mid-run.
    taint: object = None
    #: exclude this batch's service time from the cost-model EWMA (it
    #: faulted / stalled — retries must not poison admission estimates)
    cost_excluded: bool = False
    #: continuous-batching linkage: a *chaser* replays joiners from step 0
    #: up to its target's boundary (``chaser_for`` points at the parked
    #: target, whose ``parked_by`` points back); ``row_keyed`` records the
    #: per-row PRNG contract that makes join/split/regroup replayable
    #: per request; ``lineage`` accumulates the run-state's history
    chaser_for: object = None
    parked_by: object = None
    row_keyed: bool = False
    lineage: Tuple[str, ...] = ()
    #: observability: tracer track id of this run's span (0 = engine
    #: track, i.e. tracing disabled at launch) and the engine-wide batch
    #: serial the track is named after — merge/regroup/split events
    #: reference serials so lineage survives as span links in the trace
    track: int = 0
    serial: int = 0
    #: durability: boundary advances survived so far — the checkpoint
    #: cadence counter (a snapshot lands every ``checkpoint_every``-th)
    advances: int = 0


class ServeEngine:
    """Queue → batcher → interleaved executor runs → metrics."""

    def __init__(self, executor, params, store: ArtifactStore, *,
                 clock=None, max_batch: int = 8, max_wait: float = 0.0,
                 max_inflight: int = 2, scheduler="interleave",
                 adaptive_chunk: int = 4, eager: bool = False,
                 check: bool = False, admission=None, cost_model=None,
                 resilience=None, continuous: bool = False,
                 join_horizon: float = 0.5, tracer=None, registry=None,
                 telemetry: bool = False, journal=None, snapshot_dir=None,
                 checkpoint_every: int = 1):
        # lazy so repro.serve stays importable without the slo layer
        # loaded (and the layering acyclic: slo never imports the engine)
        from repro.slo.admission import LoadEstimator, ServiceCostModel
        from repro.slo.policy import resolve_policy
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        if adaptive_chunk < 1:
            raise ValueError(f"adaptive_chunk must be >= 1, got "
                             f"{adaptive_chunk}")
        self.executor = executor
        #: the weights every program of this engine reads: on the TPU the
        #: model's matrices rounded to bfloat16 here, once, rather than at
        #: the start of each program (``diffusion.mxu_params``); a test
        #: double with no model configuration keeps what it was given
        cfg = getattr(executor, "cfg", None)
        self.params = (params if cfg is None
                       else diffusion.mxu_params(cfg, params))
        #: bytes of the weights that rounding made bfloat16 (0 off the TPU)
        self.weights_cast_bytes = sum(
            new.nbytes for old, new in zip(jax.tree.leaves(params),
                                           jax.tree.leaves(self.params))
            if new.dtype != old.dtype)
        self.store = store
        self.clock = clock if clock is not None else WallClock()
        self.queue = RequestQueue(self.clock)
        self.batcher = MicroBatcher(self.queue, store, max_batch=max_batch,
                                    max_wait=max_wait)
        #: observability (repro.obs): one MetricsRegistry backs every
        #: ServerMetrics counter plus the controller/backlog time series;
        #: the tracer (NULL_TRACER by default — all hooks are no-ops)
        #: records the full batch lifecycle as Chrome trace events, one
        #: track per in-flight batch.  ``telemetry=True`` additionally
        #: asks fused adaptive runs to carry their per-step proxy values
        #: on device (read only at finish — zero extra host syncs) so
        #: every served request gets a :class:`repro.obs.CacheReport` in
        #: ``cache_reports``.
        self.registry = registry if registry is not None else \
            MetricsRegistry()
        self.metrics = ServerMetrics(registry=self.registry)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if tracer is not None:
            self.batcher.tracer = tracer
        self.telemetry = bool(telemetry)
        self.cache_reports: Dict[int, object] = {}   # rid → CacheReport
        self._serial = 0                      # batch serial (trace tracks)
        #: the scheduling policy object; ``scheduler`` may be a built-in
        #: name ("interleave"/"fcfs"/"edf") or any
        #: repro.slo.SchedulingPolicy (e.g. ElasticPolicy(controller))
        self.policy = resolve_policy(scheduler)
        self.scheduler = self.policy.name
        self.admission = admission            # repro.slo.AdmissionController
        self.cost_model = (cost_model if cost_model is not None
                           else ServiceCostModel())
        self.load = LoadEstimator(self.cost_model,
                                  batch_factor=max_batch)
        if not (0.0 <= join_horizon <= 1.0):
            raise ValueError(f"join_horizon must be in [0, 1], got "
                             f"{join_horizon}")
        self.max_inflight = max_inflight
        self.adaptive_chunk = adaptive_chunk
        self.eager = eager
        self.check = check
        #: continuous in-flight batching: waiting compatible requests may
        #: join an in-flight run at its next boundary (catch-up chaser +
        #: run-state merge), and τ>0 fused batches regroup by realized
        #: mask signature.  Requires an executor with ``split_run``/
        #: ``merge_runs`` and a deterministic solver; launches switch to
        #: per-row PRNG keys so each request replays as
        #: ``generate(params, batch_key([seed]), 1)``.
        self.continuous = continuous
        #: latest join point as a fraction of the run (a joiner replays
        #: the target's past steps, so late joins cost more than they
        #: save)
        self.join_horizon = float(join_horizon)
        #: repro.resilience.ResiliencePolicy, or None — None keeps the
        #: exact pre-resilience behavior: no health reads, no watchdog,
        #: BatchFaults propagate, the stall guard raises
        self.resilience = resilience
        if resilience is not None and resilience.entry_fault_threshold \
                is not None:
            store.health.fault_threshold = resilience.entry_fault_threshold
        self.results: Dict[int, np.ndarray] = {}
        self.records: List[BatchRecord] = []
        self.shed: Dict[int, Tuple[str, float]] = {}   # rid → (reason, t)
        self._inflight: List[_Inflight] = []
        self._rids: set = set()               # every rid ever submitted
        self._attempts: Dict[int, int] = {}   # rid → fault retry count
        self._requeues: Dict[int, int] = {}   # rid → survivor re-queues
        self._level: Dict[int, int] = {}      # rid → degradation level
        self._origin: Dict[int, str] = {}     # rid → group first submitted
        #: durability (repro.durable): optional write-ahead journal +
        #: boundary run-state snapshots.  Both lazily imported so an
        #: engine without them never touches msgpack; ``journal`` may be
        #: a path or a constructed RequestJournal; ``recover()`` replays
        #: both after a restart.
        if checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got "
                             f"{checkpoint_every}")
        self.checkpoint_every = int(checkpoint_every)
        self.journal = None
        if journal is not None:
            from repro.durable import RequestJournal
            self.journal = (journal if isinstance(journal, RequestJournal)
                            else RequestJournal(str(journal)))
        self._snapshots = None
        if snapshot_dir is not None:
            if not getattr(executor, "supports_export", False):
                raise ValueError(
                    "snapshot_dir= needs an executor with run-state "
                    "export/import seams (supports_export)")
            from repro.durable import SnapshotStore
            self._snapshots = SnapshotStore(str(snapshot_dir))
        self._done: set = set()               # journal-known finishes
        self._sweep_needed = (admission is not None
                              or resilience is not None)

    # -- submission ----------------------------------------------------------

    def submit(self, *reqs: Request) -> None:
        """Enqueue requests (arrival stamped now unless preset).

        Invalid submissions become *reasoned outcomes*, never exceptions
        that would kill a serving loop mid-stream: an unknown policy name
        is recorded as a ``no_entry`` shed (``outcome(rid)`` reports it),
        and a duplicate rid — against *every* rid ever submitted (queued,
        in flight, done, or earlier in this very call), since a duplicate
        would silently overwrite its sibling's result — is dropped and
        counted, leaving the original request's outcome untouched.  The
        one exception: with a journal, a request it cannot hold whole
        (a text memory) raises ``ValueError`` before any of the call's
        requests is taken."""
        now = self.clock.now()
        if self.journal is not None:
            for r in reqs:
                if r.memory is not None:
                    raise ValueError(
                        f"request {r.rid} carries a text memory, which the "
                        "journal does not hold: a replay would serve it "
                        "unconditioned; serve text-conditioned requests "
                        "without a journal")
        accepted = []
        recs = []
        for r in reqs:
            if r.rid in self._rids:
                self.metrics.observe_reject("duplicate_rid")
                self.tracer.instant("reject", rid=r.rid,
                                    reason="duplicate_rid")
                continue
            if r.policy not in self.store:
                self._rids.add(r.rid)
                self.shed[r.rid] = ("no_entry", now)
                self.metrics.observe_shed(r, "no_entry", now)
                self.metrics.observe_reject("no_entry")
                self.tracer.instant("reject", rid=r.rid, reason="no_entry")
                if self.journal is not None:
                    recs.append(self._submit_rec(r, now))
                    recs.append({"ev": "shed", "rid": r.rid,
                                 "reason": "no_entry", "t": now})
                continue
            self._rids.add(r.rid)
            accepted.append(r)
            if self.journal is not None:
                recs.append(self._submit_rec(r, now))
            if getattr(r, "max_tau", None) is not None:
                self._sweep_needed = True
            if self.tracer.enabled:
                self.tracer.instant("submit", rid=r.rid, policy=r.policy,
                                    priority=r.priority)
        if recs:
            # the write-ahead contract: a submission is on disk (fsynced)
            # before the queue can act on it — a crash after this line
            # cannot lose an accepted request
            self.journal.append_many(recs, sync=True)
        self.queue.submit_many(accepted)

    def outcome(self, rid: int):
        """Explicit fate of a submitted request — requests are never
        silently dropped: ``("done", latent)``, ``("shed", reason)``, or
        ``("pending", None)``.  After a restart the *verdict* of a
        pre-crash finish survives via the journal — ``("done", None)``:
        the latent payload itself is not journaled (it was delivered
        before the crash), but the request is provably not lost."""
        if rid not in self._rids:
            raise KeyError(f"rid {rid} was never submitted")
        if rid in self.results:
            return ("done", self.results[rid])
        if rid in self.shed:
            return ("shed", self.shed[rid][0])
        if rid in self._done:
            return ("done", None)
        return ("pending", None)

    # -- durability plumbing --------------------------------------------------

    def _submit_rec(self, r: Request, now: float) -> Dict:
        """The journaled form of one submission — everything needed to
        rebuild the Request verbatim after a restart (original arrival
        included, so re-admission never launders queue wait)."""
        rec = {"ev": "submit", "rid": r.rid, "seed": int(r.seed),
               "policy": r.policy,
               "arrival": float(r.arrival) if r.arrival is not None
               else float(now)}
        if r.label is not None:
            rec["label"] = int(r.label)
        if r.priority:
            rec["priority"] = int(r.priority)
        if r.slo is not None:
            rec["slo"] = {"deadline": r.slo.deadline,
                          "max_tau": r.slo.max_tau, "cls": r.slo.cls}
        return rec

    def _journal(self, ev: str, *, sync: bool = True, **fields) -> None:
        if self.journal is not None:
            self.journal.append(ev, sync=sync, **fields)

    def _drop_snapshot(self, fl: "_Inflight") -> None:
        """The run left flight (finished / faulted / merged away /
        regrouped / split) — its snapshot no longer describes anything."""
        if self._snapshots is not None:
            self._snapshots.drop(fl.serial)

    # -- SLO sweep (quality floors + admission) -------------------------------

    def _backlog_seconds(self, now: float) -> float:
        """Load estimate: queued steps (batch-amortized) + in-flight
        remaining steps, priced at the calibrated per-step cost."""
        from repro.slo.slo import remaining_steps
        queued = []
        for g in self.queue.ready_groups(now):
            for r in self.queue.peek(g, now):
                e = self.store.resolve_entry_for(g, r)
                queued.append(e.plan.num_steps if e is not None else 0)
        inflight = [remaining_steps(fl.rs) for fl in self._inflight]
        return self.load.backlog_seconds(queued, inflight)

    def _shed(self, req: Request, reason: str, now: float) -> None:
        self.queue.take_rids(req.policy, [req.rid], now)
        self.shed[req.rid] = (reason, now)
        self.metrics.observe_shed(req, reason, now)
        self.tracer.instant("shed", rid=req.rid, reason=reason)
        self._journal("shed", rid=req.rid, reason=reason, t=float(now))

    def _slo_sweep(self, now: float) -> None:
        """Walk the ready queue: shed requests whose quality floor no
        registered rung satisfies, then let the admission controller
        shed/defer against the backlog estimate.  The backlog is
        snapshotted once per sweep so decisions are order-independent."""
        if not self._sweep_needed:
            return
        backlog = None
        for g in list(self.queue.ready_groups(now)):
            for r in self.queue.peek(g, now):
                entry = self.store.resolve_entry_for(g, r)
                if entry is None:
                    # distinguish "this entry was marked unhealthy by the
                    # fault registry" from "no rung satisfies the floor"
                    reason = ("unhealthy_entry"
                              if not self.store.health.is_servable(g)
                              else "quality_floor")
                    self._shed(r, reason, now)
                    continue
                if self.admission is None:
                    continue
                if backlog is None:
                    backlog = self._backlog_seconds(now)
                    self.registry.series("slo.backlog_s").record(now,
                                                                 backlog)
                est = self.cost_model.estimate(entry.plan.num_steps,
                                               group=entry.name)
                d = self.admission.decide(r, now, backlog_s=backlog,
                                          est_service_s=est)
                if d.action == "shed":
                    self._shed(r, d.reason, now)
                elif d.action == "defer":
                    self.queue.take_rids(g, [r.rid], now)
                    self.metrics.observe_defer(r, now)
                    self.tracer.instant("defer", rid=r.rid,
                                        retry_at=d.retry_at)
                    self.queue.resubmit(r, d.retry_at)

    # -- scheduling ----------------------------------------------------------

    def _active_inflight(self) -> int:
        """In-flight runs that actually advance — parked join targets
        wait on their chaser and don't occupy a timeslice."""
        return sum(1 for f in self._inflight if f.parked_by is None)

    def _admit(self, now: float) -> None:
        while self._active_inflight() < self.max_inflight:
            mb = self.batcher.next_batch(now)
            if mb is None:
                break
            self._launch(mb, now)
        if self.continuous:
            self._join_waiting(now)

    def _begin_track(self, mb: MicroBatch, kind: str, *, parent=None,
                     via=None, chaser_for=None) -> Tuple[int, int]:
        """Allocate the next batch serial and — when tracing — a tracer
        track with an open ``run`` span.  Lineage events (join / regroup /
        split_retry) name the parent serial in the child span's args, the
        trace-side mirror of ``BatchRecord.lineage``."""
        self._serial += 1
        serial, track = self._serial, 0
        if self.tracer.enabled:
            track = self.tracer.new_track(
                f"batch#{serial} {mb.entry.name} b{mb.bucket}")
            args = {"group": mb.entry.name, "version": mb.entry.version,
                    "bucket": mb.bucket, "kind": kind,
                    "rids": list(mb.rids)}
            if parent is not None:
                args["parent"] = parent
            if via is not None:
                args["via"] = via
            if chaser_for is not None:
                args["chaser_for"] = chaser_for
            self.tracer.begin(track, "run", **args)
        return serial, track

    def _batch_cond(self, mb: MicroBatch) -> Dict:
        """The batch's conditioning as the executor takes it, stacked in
        row order and put on the device under a ``serve.cond`` span with
        its byte count: ``label`` (B,) int32 where a request has one (0
        stands in for the others), and ``memory``, a text memory
        ``{"text": (B, M, width), "mask": (B, M) bool}`` whose mask marks
        each request's first ``memory_len`` rows, where requests carry
        one."""
        reqs = mb.requests
        with self.tracer.span(0, "serve.cond") as span:
            host = {}
            if any(r.label is not None for r in reqs):
                host["label"] = np.asarray(
                    [0 if r.label is None else int(r.label) for r in reqs],
                    np.int32)
            if any(r.memory is not None for r in reqs):
                if any(r.memory is None for r in reqs):
                    raise ValueError(
                        f"batch {list(mb.rids)} mixes requests with and "
                        "without a text memory")
                text = np.stack([np.asarray(r.memory, np.float32)
                                 for r in reqs])
                lens = np.asarray([text.shape[1] if r.memory_len is None
                                   else int(r.memory_len) for r in reqs])
                host["memory"] = {
                    "text": text,
                    "mask": np.arange(text.shape[1])[None] < lens[:, None]}
            span.update(bytes=sum(a.nbytes for a in jax.tree.leaves(host)))
            return jax.tree.map(jnp.asarray, host)

    def _launch(self, mb: MicroBatch, now: float, *,
                chaser_for=None) -> _Inflight:
        entry = mb.entry
        key = batch_key(mb.seeds)
        extra = {}
        row_keyed = False
        if (self.continuous and not self.eager
                and getattr(self.executor, "supports_split", False)):
            # per-row PRNG contract: row i's latent is the B=1 draw of
            # its own key, so join/split/regroup never change any
            # request's bits and replay is per-request
            extra["row_keys"] = [batch_key([s]) for s in mb.seeds]
            row_keyed = True
        # ``serve.launch`` is named by the serial the batch is about to get
        with self.tracer.span(0, "serve.launch", serial=self._serial + 1,
                              bucket=mb.bucket, rids=list(mb.rids)):
            cond = self._batch_cond(mb)
            if self.eager:
                kind, rs = "eager", _EagerState(cond)
            elif entry.adaptive and self._fused_adaptive:
                kind = "adaptive_fused"
                if self.telemetry:
                    # decision-trace carry rides the fused program; passed
                    # only when on so executors (and test fakes) without the
                    # kwarg keep working
                    extra["telemetry"] = True
                rs = self.executor.start_adaptive_fused_run(
                    self.params, key, mb.bucket, schedule=entry.schedule,
                    tau=entry.tau, proxy_map=entry.proxy_map,
                    pool=entry.pool(), k_max=entry.k_max, **cond,
                    **extra)
            elif entry.adaptive:
                kind = "adaptive"
                rs = self.executor.start_adaptive_run(
                    self.params, key, mb.bucket, schedule=entry.schedule,
                    tau=entry.tau, proxy_map=entry.proxy_map,
                    pool=entry.pool(), k_max=entry.k_max, **cond,
                    **extra)
            else:
                kind = "plan"
                rs = self.executor.start_run(
                    self.params, key, mb.bucket, plan=entry.plan,
                    schedule=entry.schedule, **cond, **extra)
        for r in mb.requests:
            r.started = now
        serial, track = self._begin_track(
            mb, kind,
            chaser_for=chaser_for.serial if chaser_for is not None
            else None)
        fl = _Inflight(mb=mb, kind=kind, rs=rs,
                       row_keyed=row_keyed, chaser_for=chaser_for,
                       track=track, serial=serial)
        self._inflight.append(fl)
        # progress event, not an ack — flushed, not fsynced: losing it in
        # a crash only re-launches the batch from its submit records
        self._journal("launch", sync=False, serial=serial, kind=kind,
                      entry=entry.name, version=entry.version,
                      bucket=mb.bucket, rids=list(mb.rids), t=float(now))
        return fl

    @property
    def _fused_adaptive(self) -> bool:
        """Serve adaptive entries through the fused on-device path when
        the executor offers it (scannable solver): one program per entry
        instead of pool-size × steps of dispatches, zero per-step
        decision syncs."""
        return bool(getattr(self.executor, "supports_fused_adaptive",
                            False))

    def _advance_args(self, fl: _Inflight) -> Dict:
        """Args of a ``serve.advance`` span: the batch, its run kind, the
        step it starts from and, for a plan, the segment's label."""
        args = {"serial": fl.serial, "kind": fl.kind}
        step = getattr(fl.rs, "step", None)
        if step is not None:
            args["step_from"] = int(step)
        if fl.kind == "plan":
            plan = getattr(fl.rs, "plan", None)
            ri = getattr(fl.rs, "run_index", None)
            if plan is not None and ri is not None \
                    and hasattr(plan, "run_label"):
                try:
                    args["segment"] = plan.run_label(int(ri))
                except (IndexError, TypeError):
                    pass
        return args

    def _advance(self, fl: _Inflight) -> None:
        """One advance unit under a ``serve.advance`` span on the batch's
        track.  ``new_program`` on its end says whether the executor's
        program table missed (a tick that compiled); the span stays
        matched when the advance raises (fault injection)."""
        tr = self.tracer
        recording = tr.enabled or profiling()
        args = self._advance_args(fl) if recording else {}
        programs = (self.executor.compiled_variant_count() if recording
                    else 0)
        with tr.span(fl.track, "serve.advance", **args) as span:
            self._dispatch(fl)
            if recording:
                end = {"new_program": int(
                    self.executor.compiled_variant_count() != programs)}
                step = getattr(fl.rs, "step", None)
                if step is not None:
                    end["step_to"] = int(step)
                span.update(**end)

    def _dispatch(self, fl: _Inflight) -> None:
        entry = fl.mb.entry
        if fl.kind == "plan":
            fl.rs = self.executor.advance_run(self.params, fl.rs,
                                              check=self.check)
        elif fl.kind == "adaptive_fused":
            # the whole chunk is one program dispatch — the timeslice
            # granularity costs no extra host round-trips.  A chaser
            # clamps to its parked target's boundary so the two align
            # exactly for the merge.
            n = self.adaptive_chunk
            if fl.chaser_for is not None:
                n = min(n, fl.chaser_for.rs.step - fl.rs.step)
            fl.rs = self.executor.advance_adaptive_fused(
                self.params, fl.rs, n_steps=max(n, 1))
        elif fl.kind == "adaptive":
            n = self.adaptive_chunk
            if fl.chaser_for is not None:
                n = min(n, fl.chaser_for.rs.step - fl.rs.step)
            for _ in range(max(n, 1)):
                if fl.rs.done:
                    break
                fl.rs = self.executor.advance_adaptive_run(self.params,
                                                           fl.rs)
        else:                                  # eager escape hatch
            key = batch_key(fl.mb.seeds)
            fl.rs.x = self.executor.sample(
                self.params, key, fl.mb.bucket, schedule=entry.schedule,
                **fl.rs.cond)

    # -- continuous batching (join / regroup / coalesce) ---------------------

    @staticmethod
    def _p2_groups(rows: List[int]) -> List[List[int]]:
        """Decompose a row list into power-of-two-sized groups, largest
        first — every sub-run lands on an already-compiled bucket shape,
        so split/regroup never grow ``xla_program_count``."""
        out = []
        rows = list(rows)
        while rows:
            take = 1
            while take * 2 <= len(rows):
                take *= 2
            out.append(rows[:take])
            rows = rows[take:]
        return out

    def _is_linked(self, fl: _Inflight) -> bool:
        return (fl.parked_by is not None or fl.chaser_for is not None
                or any(o.chaser_for is fl for o in self._inflight))

    def _unlink(self, fl: _Inflight) -> None:
        """Detach a run leaving flight (fault/abort) from any join pair
        so its partner doesn't wait forever: a dying chaser unparks its
        target; a dying target releases its chaser to run to completion
        on its own."""
        if fl.chaser_for is not None and fl.chaser_for.parked_by is fl:
            fl.chaser_for.parked_by = None
        fl.chaser_for = None
        if fl.parked_by is not None:
            fl.parked_by.chaser_for = None
            fl.parked_by = None
        for o in self._inflight:
            if o.chaser_for is fl:
                o.chaser_for = None

    def _join_waiting(self, now: float) -> None:
        """Continuous feeder: waiting compatible requests join an
        in-flight run at its next boundary instead of queuing for a
        fresh slot.  The join is a *catch-up chaser*: the joiners launch
        as their own p2 batch at step 0 (their queue wait ends here),
        the target parks, the chaser replays to the target's boundary
        (clamped advances), and the two run-states merge — pure row
        concat, bit-identical per row — once aligned."""
        from repro.slo.slo import remaining_steps
        if not getattr(self.executor, "supports_split", False):
            return
        for fl in list(self._inflight):
            if (fl.kind == "eager" or not fl.row_keyed or fl.rs.done
                    or self._is_linked(fl)):
                continue
            steps = fl.mb.entry.plan.num_steps
            done_steps = steps - remaining_steps(fl.rs)
            if done_steps > self.join_horizon * steps:
                continue                      # too far gone to chase
            joiners = self.batcher.take_join(now, fl.mb.entry,
                                             fl.mb.bucket)
            if not joiners:
                continue
            mb = MicroBatch(requests=tuple(joiners), entry=fl.mb.entry,
                            formed_at=now)
            chaser = self._launch(mb, now, chaser_for=fl)
            fl.parked_by = chaser
            for r in joiners:
                r.joined_at = now
            self.metrics.observe_join(len(joiners))
            if self.tracer.enabled:
                self.tracer.instant(
                    "join", tid=fl.track, at_step=int(fl.rs.step),
                    chaser=chaser.serial,
                    rids=[r.rid for r in joiners])
            self._try_merge(chaser)           # step-0 target: merge now

    def _merge_pair(self, a: _Inflight, b: _Inflight,
                    tag: str) -> _Inflight:
        """Merge two aligned in-flight runs (rows of ``a`` first, matching
        ``merge_runs``'s concat order) into one new in-flight record."""
        merged_rs = self.executor.merge_runs([a.rs, b.rs])
        mb = MicroBatch(requests=a.mb.requests + b.mb.requests,
                        entry=a.mb.entry, formed_at=a.mb.formed_at)
        taint = None
        if a.taint is not None or b.taint is not None:
            ta = (a.taint if a.taint is not None
                  else np.ones(a.mb.bucket, bool))
            tb = (b.taint if b.taint is not None
                  else np.ones(b.mb.bucket, bool))
            taint = np.concatenate([ta, tb])
        rids = ",".join(str(r) for r in b.mb.rids)
        # the merged run keeps a's track/serial — in the trace, b's span
        # ends here with a "merged into a" outcome (a span link by serial)
        if self.tracer.enabled and b.track:
            self.tracer.end(b.track, "run", outcome=f"merged:{tag}",
                            into=a.serial)
        # b's run-state is gone; a's snapshot (if any) is superseded at
        # its next boundary checkpoint and the rid-vs-pending staleness
        # check guards the window in between
        self._drop_snapshot(b)
        merged = _Inflight(
            mb=mb, kind=a.kind, rs=merged_rs, taint=taint,
            cost_excluded=a.cost_excluded or b.cost_excluded,
            row_keyed=True,
            lineage=a.lineage + b.lineage
            + (f"{tag}@{a.rs.step}:{rids}",),
            track=a.track, serial=a.serial)
        idx = self._inflight.index(a)
        self._inflight[idx] = merged
        self._inflight.remove(b)
        self.metrics.observe_merge(kind=tag)
        self.metrics.observe_lineage(tag)
        return merged

    def _try_merge(self, chaser: _Inflight) -> None:
        target = chaser.chaser_for
        if target is None or chaser.rs.step != target.rs.step:
            return
        target.parked_by = None
        chaser.chaser_for = None
        self._merge_pair(target, chaser, "join")

    def _maybe_regroup(self, fl: _Inflight) -> None:
        """At a fused chunk boundary, split a τ>0 batch whose rows now
        *want* different masks into per-signature sub-runs (p2 sizes
        only): each sub-run's executed mask is the AND over fewer rows,
        so cache-willing rows stop being dragged to full compute by one
        conservative neighbor."""
        if (fl.kind != "adaptive_fused" or fl.mb.entry.tau <= 0
                or fl.mb.bucket <= 1 or not fl.row_keyed or fl.rs.done
                or self._is_linked(fl)
                or not getattr(self.executor, "supports_split", False)):
            return
        sigs = fl.rs.row_signatures()
        if sigs is None or len(set(sigs)) <= 1:
            return
        bysig: Dict[tuple, List[int]] = {}
        for j, s in enumerate(sigs):
            bysig.setdefault(s, []).append(j)
        groups = []
        for s in sorted(bysig):               # deterministic order
            groups.extend(self._p2_groups(bysig[s]))
        subs = self.executor.split_run(fl.rs, groups)
        if self.tracer.enabled and fl.track:
            self.tracer.end(fl.track, "run",
                            outcome=f"regroup:{len(groups)}")
        self._drop_snapshot(fl)
        idx = self._inflight.index(fl)
        repl = []
        for g, sub in zip(groups, subs):
            mb = MicroBatch(
                requests=tuple(fl.mb.requests[j] for j in g),
                entry=fl.mb.entry, formed_at=fl.mb.formed_at)
            rids = ",".join(str(r.rid) for r in mb.requests)
            serial, track = self._begin_track(mb, fl.kind,
                                              parent=fl.serial,
                                              via="regroup")
            repl.append(_Inflight(
                mb=mb, kind=fl.kind, rs=sub,
                taint=(None if fl.taint is None
                       else fl.taint[np.asarray(g)]),
                cost_excluded=fl.cost_excluded, row_keyed=True,
                lineage=fl.lineage
                + (f"regroup@{fl.rs.step}:{rids}",),
                track=track, serial=serial))
        self._inflight[idx:idx + 1] = repl
        self.metrics.observe_regroup(len(repl))
        self.metrics.observe_lineage("regroup", len(repl))

    def _coalesce(self) -> None:
        """Opportunistic reverse of regroup: two unlinked runs of the
        same entry/version/kind, aligned at the same step with equal
        buckets, merge back into one (2·b stays p2, so still on budget).
        A τ>0 fused pair must currently want the same mask — merging
        divergent rows would re-impose the shared-mask AND regroup just
        removed."""
        if not getattr(self.executor, "supports_split", False):
            return
        for a in list(self._inflight):
            if a not in self._inflight:
                continue
            if (a.kind == "eager" or not a.row_keyed or a.rs.done
                    or self._is_linked(a)):
                continue
            for b in list(self._inflight):
                if (b is a or b not in self._inflight
                        or a not in self._inflight):
                    continue
                if (b.kind != a.kind or not b.row_keyed or b.rs.done
                        or self._is_linked(b)
                        or b.mb.entry.name != a.mb.entry.name
                        or b.mb.entry.version != a.mb.entry.version
                        or b.mb.bucket != a.mb.bucket
                        or a.mb.bucket + b.mb.bucket
                        > self.batcher.max_batch
                        or b.rs.step != a.rs.step):
                    continue
                if a.kind == "adaptive_fused" and a.mb.entry.tau > 0:
                    sa, sb = a.rs.row_signatures(), b.rs.row_signatures()
                    if sa is None or sb is None or set(sa) != set(sb) \
                            or len(set(sa)) != 1:
                        continue
                self._merge_pair(a, b, "coalesce")

    # -- fault handling (degrade, don't die) ---------------------------------

    def _read_health(self, fl: _Inflight):
        """Merge the run state's sentinel flags into the in-flight taint
        record.  Returns the merged (B,) bool array, or None when neither
        the sentinels nor the chaos harness flagged anything.  Newly
        poisoned rows are counted as one fault event against the group."""
        flags = getattr(fl.rs, "healthy", None)
        if flags is None:
            return fl.taint
        cur = np.asarray(jax.device_get(flags)).astype(bool)
        if fl.taint is not None:
            cur = cur & fl.taint
        prev = fl.taint
        newly = (~cur) if prev is None else (prev & ~cur)
        if newly.any():
            self.metrics.observe_fault(fl.mb.group, NAN_LATENT)
            self.store.report_fault(fl.mb.group, NAN_LATENT)
        fl.taint = cur
        return cur

    def _fault_abort(self, fl: _Inflight, kind: str, sample_flags,
                     now: float, *, count: bool = True) -> None:
        """Abandon an in-flight batch after a fault.  Rows flagged healthy
        (per-sample resolution) or all rows (no resolution) *survive*:
        they re-queue at their original arrival time (``resubmit`` never
        touches ``arrival``, so queue-wait accounting keeps charging from
        first arrival).  Poisoned rows go down the degradation ladder via
        :meth:`_retry_or_fail`.  Survivors that keep landing in aborted
        batches are bounded too — past the retry budget they join the
        fault path instead of looping forever."""
        mb = fl.mb
        self._unlink(fl)
        self._drop_snapshot(fl)
        if self.tracer.enabled and fl.track:
            self.tracer.end(fl.track, "run", outcome=f"fault:{kind}")
        if count:
            self.metrics.observe_fault(mb.group, kind)
            self.store.report_fault(mb.group, kind)
        flags = sample_flags if sample_flags is not None else fl.taint
        budget = self.resilience.retry.max_retries
        for j, r in enumerate(mb.requests):
            ok = True if flags is None else bool(flags[j])
            if not ok:
                self._retry_or_fail(r, kind, now)
                continue
            n = self._requeues.get(r.rid, 0) + 1
            self._requeues[r.rid] = n
            if n > budget + 1:
                # repeatedly a bystander of dying batches — stop looping
                self._retry_or_fail(r, kind, now)
            else:
                r.started = None
                self.queue.resubmit(r, now)
                self.metrics.observe_requeue(1)

    def _retry_or_fail(self, r: Request, kind: str, now: float) -> None:
        """Bounded retry of one faulted request, stepping down the
        degradation ladder (current rung → τ=0 → no_cache) with
        deterministic backoff; past the budget the request ends as a
        reasoned terminal outcome (``fault:<kind>``), counted like any
        shed — never a crash, never a silent drop."""
        pol = self.resilience
        att = self._attempts.get(r.rid, 0) + 1
        self._attempts[r.rid] = att
        if att > pol.retry.max_retries:
            self.shed[r.rid] = (f"fault:{kind}", now)
            self.metrics.observe_shed(r, f"fault:{kind}", now)
            self.tracer.instant("shed", rid=r.rid, reason=f"fault:{kind}")
            self._journal("shed", rid=r.rid, reason=f"fault:{kind}",
                          t=float(now))
            return
        origin = self._origin.setdefault(r.rid, r.policy)
        if pol.degrade:
            level = self._level.get(r.rid, 0) + 1
            target = self.store.degraded_entry_name(origin, level)
            if target is None:    # no τ=0 form for this group: skip a rung
                level = 2
                target = self.store.degraded_entry_name(origin, level)
            self._level[r.rid] = level
            if target != r.policy:
                r.policy = target
                self.metrics.observe_degrade(r)
        r.started = None
        self.metrics.observe_retry(r)
        self.tracer.instant("retry", rid=r.rid, attempt=att,
                            policy=r.policy)
        self._journal("retry", sync=False, rid=r.rid, attempt=att,
                      policy=r.policy, level=self._level.get(r.rid, 0),
                      t=float(now))
        self.queue.resubmit(r, now + pol.retry.delay(att, r.rid))

    def _stall_shed(self, reason: str, now: float) -> None:
        """Degrade-don't-die replacement for the stall guard: every queued
        request gets an explicit shed outcome instead of the engine
        raising out of its serving loop."""
        recs = []
        for r in self.queue.drain_all():
            self.shed[r.rid] = (reason, now)
            self.metrics.observe_shed(r, reason, now)
            self.tracer.instant("shed", rid=r.rid, reason=reason)
            recs.append({"ev": "shed", "rid": r.rid, "reason": reason,
                         "t": float(now)})
        if recs and self.journal is not None:
            self.journal.append_many(recs, sync=True)

    def _watchdog_deadline(self, steps: int, group: str,
                           bucket: Optional[int] = None) -> float:
        # keyed on the same (rung, bucket) the cost model learns on, so
        # a ladder move or a regrouped bucket size gets its own deadline
        est = self.cost_model.estimate(max(int(steps), 1), group=group,
                                       bucket=bucket)
        return self.resilience.deadline(est)

    def _advance_guarded(self, i: int, fl: _Inflight) -> bool:
        """Advance under the fault net: a ``BatchFault`` raised
        mid-advance, a blown watchdog deadline, or sentinel-flagged rows
        all route into the recovery path instead of propagating.  Returns
        True when the batch was aborted (``fl`` removed from flight)."""
        from repro.slo.slo import remaining_steps
        pol = self.resilience
        before = self.clock.now()
        steps_before = remaining_steps(fl.rs)
        try:
            self._advance(fl)
        except BatchFault as bf:
            self._inflight.pop(i)
            self._fault_abort(fl, bf.kind, bf.sample_flags,
                              self.clock.now())
            return True
        after = self.clock.now()
        if pol.watchdog_factor is not None:
            steps_adv = steps_before - remaining_steps(fl.rs)
            deadline = self._watchdog_deadline(steps_adv, fl.mb.group,
                                               fl.mb.bucket)
            if after - before > deadline:
                self.tracer.instant("watchdog_fire", tid=fl.track,
                                    group=fl.mb.group,
                                    elapsed_s=after - before,
                                    deadline_s=deadline)
                if fl.rs.done:
                    # too late to re-queue — deliver, but keep the stall
                    # out of the cost model and on the books
                    fl.cost_excluded = True
                    self.metrics.observe_fault(fl.mb.group, STUCK_BATCH)
                    self.store.report_fault(fl.mb.group, STUCK_BATCH)
                else:
                    self._inflight.pop(i)
                    self._fault_abort(fl, STUCK_BATCH, None, after)
                    return True
        flags = self._read_health(fl)
        if flags is not None and not flags.any() and not fl.rs.done:
            # every row is poisoned — nothing left worth carrying to the
            # finish line (already counted by _read_health)
            self._inflight.pop(i)
            self._fault_abort(fl, NAN_LATENT, flags, after, count=False)
            return True
        if (flags is not None and not flags.all() and not fl.rs.done
                and getattr(pol, "split_retry", False)
                and fl.mb.bucket > 1 and fl.kind != "eager"
                and not self._is_linked(fl)
                and getattr(self.executor, "supports_split", False)):
            # per-row retry within a continuing batch: faulted rows split
            # out and sent down the ladder NOW, survivors keep their
            # run-state (p2 sub-batches — no new shapes) instead of
            # dragging dead rows to the finish line
            self._split_retry(i, fl, flags, after)
            return True
        return False

    def _split_retry(self, i: int, fl: _Inflight, flags,
                     now: float) -> None:
        good = [j for j in range(fl.mb.bucket) if flags[j]]
        bad = [j for j in range(fl.mb.bucket) if not flags[j]]
        groups = self._p2_groups(good)
        subs = self.executor.split_run(fl.rs, groups)
        if self.tracer.enabled and fl.track:
            self.tracer.end(fl.track, "run",
                            outcome=f"split_retry:{len(bad)}")
        self._drop_snapshot(fl)
        self._inflight.pop(i)
        for g, sub in zip(groups, subs):
            mb = MicroBatch(
                requests=tuple(fl.mb.requests[j] for j in g),
                entry=fl.mb.entry, formed_at=fl.mb.formed_at)
            rids = ",".join(str(r.rid) for r in mb.requests)
            serial, track = self._begin_track(mb, fl.kind,
                                              parent=fl.serial,
                                              via="split_retry")
            self._inflight.append(_Inflight(
                mb=mb, kind=fl.kind, rs=sub, taint=None,
                cost_excluded=fl.cost_excluded, row_keyed=fl.row_keyed,
                lineage=fl.lineage
                + (f"split_retry@{fl.rs.step}:{rids}",),
                track=track, serial=serial))
        for j in bad:
            self._retry_or_fail(fl.mb.requests[j], NAN_LATENT, now)
        self.metrics.observe_row_retry(len(bad))
        self.metrics.observe_lineage("split_retry", len(groups))

    def _finish(self, fl: _Inflight) -> None:
        """Deliver a finished batch: ``serve.finish.wait`` spans the
        ``block_until_ready`` and ``serve.finish.copy`` the host copy; the
        journal, metrics and cost-model bookkeeping is the self time of
        the caller's ``serve.finish`` span."""
        mb, rs = fl.mb, fl.rs
        with self.tracer.span(0, "serve.finish.wait"):
            x = jax.block_until_ready(rs.x)
        done = self.clock.now()
        with self.tracer.span(0, "serve.finish.copy"):
            x = np.asarray(x)
        # service time of the whole batch, snapshotted before any faulted
        # row's re-queue resets its start stamp
        service = done - mb.requests[0].started
        flags = None
        if self.resilience is not None:
            # rows are computationally independent (attention is within-
            # sample, CFG splits per sample), so a poisoned row never
            # contaminates its neighbors: deliver the healthy rows —
            # bit-identical to an uninjected run — and send only the
            # poisoned ones down the ladder
            finite = np.isfinite(x.reshape(x.shape[0], -1)).all(axis=1)
            flags = finite if fl.taint is None else (fl.taint & finite)
            if flags.all():
                flags = None
            else:
                newly = ((~flags) if fl.taint is None
                         else (fl.taint & ~flags))
                if newly.any():
                    # final-latent check found poison the sentinels had
                    # not already counted (eager/fake paths without
                    # carry flags)
                    self.metrics.observe_fault(mb.group, NAN_LATENT)
                    self.store.report_fault(mb.group, NAN_LATENT)
        delivered = []
        for j, r in enumerate(mb.requests):
            if flags is not None and not flags[j]:
                self._retry_or_fail(r, NAN_LATENT, done)
                continue
            r.finished = done
            self.results[r.rid] = x[j]
            self.metrics.observe_request(r)
            delivered.append(r)
        if delivered and self.journal is not None:
            # ack event: the finish verdict is on disk before the engine
            # moves on — outcome(rid) survives the process
            self.journal.append("finish", sync=True,
                                rids=[r.rid for r in delivered],
                                t=float(done))
        for r in delivered:
            self._done.add(r.rid)
        self._drop_snapshot(fl)
        entry = mb.entry
        num_types = len(entry.schedule.skip)
        decisions = getattr(rs, "decisions", None)
        if decisions:
            skipped = sum(len(d) for d in decisions)
            frac = 1.0 - skipped / float(entry.plan.num_steps * num_types)
        else:
            frac = entry.compute_fraction()
        self.metrics.observe_batch(mb.group, mb.bucket, frac,
                                   entry.plan.num_steps, num_types)
        # feed the calibrated per-step cost model (service time of the
        # whole batch — includes interleaving contention, which is the
        # pessimism an admission wait estimate wants); faulted/stalled
        # batches are excluded so retries don't poison admission estimates
        if flags is None and not fl.cost_excluded:
            self.cost_model.observe(mb.group, service,
                                    entry.plan.num_steps,
                                    bucket=mb.bucket)
        qcost = entry.predicted_quality_cost(decisions)
        self.metrics.observe_quality(entry.tau, qcost, n=mb.bucket)
        if self.tracer.enabled and fl.track:
            self.tracer.end(fl.track, "run", outcome="done",
                            compute_fraction=frac)
        if self.telemetry:
            # per-request cache-decision explainers; one boundary read
            # per finished batch (the fused path device_gets its decision
            # trace exactly once here — zero per-step syncs)
            reports = run_cache_reports(rs, mb.bucket,
                                        schedule=entry.schedule,
                                        tau=entry.tau)
            for j, r in enumerate(mb.requests):
                if j < len(reports) and (flags is None or flags[j]):
                    self.cache_reports[r.rid] = reports[j]
        record = BatchRecord(
            group=mb.group, version=entry.version, bucket=mb.bucket,
            rids=mb.rids, seeds=mb.seeds, labels=mb.labels,
            num_steps=entry.plan.num_steps, compute_fraction=frac,
            formed_at=mb.formed_at, finished_at=done, decisions=decisions,
            tau=entry.tau, quality_cost=qcost, lineage=fl.lineage)
        self.records.append(record)
        self.policy.on_finish(self, record,
                              delivered if flags is not None
                              else mb.requests, done)

    # -- durability: boundary checkpoints + restart recovery ------------------

    def _maybe_checkpoint(self, fl: _Inflight) -> None:
        """Count a survived boundary advance; every
        ``checkpoint_every``-th one snapshots the run.  Eager runs have
        no boundaries (one advance = the whole batch) and finished runs
        are about to deliver — neither checkpoints."""
        if self._snapshots is None or fl.kind == "eager" or fl.rs.done:
            return
        fl.advances += 1
        if fl.advances % self.checkpoint_every:
            return
        self._checkpoint(fl)

    def _checkpoint(self, fl: _Inflight) -> None:
        """Snapshot one in-flight run (arrays via the executor's export
        seam, provenance-stamped meta via the entry).  Degrade, don't
        die: a failed write is counted and traced, never raised — the
        batch just loses restore coverage until the next boundary."""
        now = self.clock.now()
        entry = fl.mb.entry
        try:
            kind, arrays, static = self.executor.export_run(fl.rs)
            meta = dict(entry.provenance(), kind=kind, serial=fl.serial,
                        static=static, rids=list(fl.mb.rids),
                        seeds=[int(s) for s in fl.mb.seeds],
                        priorities=[int(r.priority)
                                    for r in fl.mb.requests],
                        formed_at=float(fl.mb.formed_at),
                        row_keyed=bool(fl.row_keyed),
                        lineage=list(fl.lineage), t=float(now))
            name, nbytes = self._snapshots.save(fl.serial, arrays, meta)
        except Exception:
            self.metrics.observe_checkpoint_error()
            return
        self.metrics.observe_checkpoint(nbytes)
        step = static.get("step", static.get("run_index", 0))
        self._journal("checkpoint", sync=False, serial=fl.serial,
                      snapshot=name, step=int(step),
                      rids=list(fl.mb.rids), t=float(now))

    def _rebuild_request(self, rec: Dict) -> Request:
        """Journal submit record → Request, verbatim (original arrival,
        label, priority, SLO)."""
        slo = None
        if rec.get("slo") is not None:
            from repro.slo import SLO
            s = rec["slo"]
            slo = SLO(deadline=s.get("deadline"),
                      max_tau=s.get("max_tau"),
                      cls=s.get("cls", "default"))
        return Request(rid=rec["rid"], seed=rec["seed"],
                       policy=rec["policy"], label=rec.get("label"),
                       priority=int(rec.get("priority", 0)), slo=slo,
                       arrival=rec.get("arrival"))

    def _refuse_snapshot(self, path: str, reason: str,
                         summary: Dict) -> None:
        """A snapshot that cannot be trusted (torn file, checksum
        mismatch, provenance drift, import failure): quarantined on disk
        and in the store's health ledger — its requests take the
        replay-from-start path, which the row-keys contract makes
        bit-identical anyway."""
        qname = self._snapshots.quarantine(path)
        self.store.health.quarantine(f"snapshot:{qname}", reason)
        summary["refused"].append((qname, reason))
        self.metrics.observe_snapshot_refused()

    def _restore_snapshot(self, path: str, pending: Dict, restored: set,
                          started: Dict, now: float,
                          summary: Dict) -> None:
        from repro.checkpoint import CheckpointError
        from repro.durable import SnapshotError
        try:
            arrays, meta = self._snapshots.load(path)
        except (CheckpointError, SnapshotError, OSError, ValueError) as e:
            self._refuse_snapshot(path, f"{type(e).__name__}: {e}",
                                  summary)
            return
        rids = list(meta.get("rids", ()))
        if not rids or any(r in restored for r in rids) \
                or not all(r in pending for r in rids):
            # superseded, not suspect: its requests already finished /
            # shed / were restored from a newer snapshot — silent delete
            self._snapshots.discard(path)
            summary["stale"] += 1
            return
        try:
            entry = self.store.get(meta.get("entry"))
        except KeyError:
            self._refuse_snapshot(
                path, f"entry {meta.get('entry')!r} no longer in store",
                summary)
            return
        prov = entry.provenance()
        for k in ("version", "schedule_fp", "plan_hash",
                  "artifact_checksum", "tau", "k_max"):
            if meta.get(k) != prov.get(k):
                self._refuse_snapshot(
                    path, f"provenance drift on {k}: snapshot "
                    f"{meta.get(k)!r} vs entry {prov.get(k)!r}", summary)
                return
        kind = meta.get("kind")
        kw = {}
        if kind == "plan":
            kw["plan"] = entry.plan
        else:
            kw.update(schedule=entry.schedule, tau=entry.tau,
                      proxy_map=entry.proxy_map, pool=entry.pool(),
                      k_max=entry.k_max)
        try:
            rs = self.executor.import_run(self.params, kind, arrays,
                                          meta["static"], **kw)
        except (KeyError, TypeError, ValueError) as e:
            self._refuse_snapshot(
                path, f"import failed: {type(e).__name__}: {e}", summary)
            return
        reqs = []
        for r in rids:
            req = self._rebuild_request(pending[r])
            req.started = started.get(r, now)
            reqs.append(req)
        mb = MicroBatch(requests=tuple(reqs), entry=entry,
                        formed_at=float(meta.get("formed_at", now)))
        serial, track = self._begin_track(mb, kind, via="restore")
        static = meta.get("static", {})
        at = int(static.get("step", static.get("run_index", 0)))
        fl = _Inflight(mb=mb, kind=kind, rs=rs,
                       row_keyed=bool(meta.get("row_keyed", False)),
                       lineage=tuple(meta.get("lineage", ()))
                       + (f"restore@{at}",),
                       track=track, serial=serial)
        self._inflight.append(fl)
        self._snapshots.adopt(serial, path)
        for r in rids:
            restored.add(r)
            pending.pop(r, None)
        summary["restored_runs"] += 1
        summary["restored_requests"] += len(rids)

    def recover(self, journal=None, snapshot_dir=None) -> Dict:
        """Restart recovery: replay the write-ahead journal, restore
        in-flight batches from their newest valid snapshots, and re-admit
        everything else at its original arrival.

        * journal verdicts seed ``outcome()`` — finished/shed requests
          stay finished/shed across the restart (``("done", None)`` for a
          pre-crash finish: the verdict survives, the delivered payload
          was the old process's to lose);
        * snapshots are scanned newest-sequence-first with rid dedup:
          a valid snapshot whose requests are all still pending restores
          as a live in-flight batch and continues through the normal
          ``advance_*`` path; an invalid one (torn, tampered, provenance
          drift) is quarantined with a reason; a superseded one is
          deleted;
        * every pending request not covered by a restored run replays
          from the start — bit-identical to never having crashed, by the
          per-row key determinism contract.

        Pass ``journal``/``snapshot_dir`` to attach durability to an
        engine constructed without it (the factory pattern of the kill
        harness); both default to whatever the constructor wired.
        Returns a JSON-safe summary and journals a ``recover`` event."""
        if journal is not None:
            from repro.durable import RequestJournal
            self.journal = (journal
                            if isinstance(journal, RequestJournal)
                            else RequestJournal(str(journal)))
        if snapshot_dir is not None:
            from repro.durable import SnapshotStore
            self._snapshots = SnapshotStore(str(snapshot_dir))
        summary: Dict = {"done": 0, "shed": 0, "restored_runs": 0,
                         "restored_requests": 0, "replayed": 0,
                         "refused": [], "stale": 0, "journal_skipped": 0}
        if self.journal is None:
            return summary
        from repro.durable import JournalState
        st = JournalState.replay(self.journal.path)
        summary["journal_skipped"] = st.skipped
        now = self.clock.now()
        for rid in st.submitted:
            self._rids.add(rid)
        self._done.update(st.done)
        self.shed.update(st.shed)
        self._attempts.update(st.attempts)
        self._level.update(st.levels)
        summary["done"] = len(st.done)
        summary["shed"] = len(st.shed)
        pending = st.pending()
        restored: set = set()
        if self._snapshots is not None:
            for path in self._snapshots.scan():
                self._restore_snapshot(path, pending, restored,
                                       st.started, now, summary)
        replay = [self._rebuild_request(rec)
                  for _, rec in sorted(
                      pending.items(),
                      key=lambda kv: (kv[1].get("arrival", 0.0),
                                      str(kv[0])))]
        if any(r.max_tau is not None for r in replay):
            self._sweep_needed = True
        self.queue.submit_many(replay)
        summary["replayed"] = len(replay)
        self.metrics.observe_recovery(summary["restored_runs"],
                                      summary["restored_requests"],
                                      summary["replayed"],
                                      summary["stale"])
        self._journal("recover", sync=True,
                      restored_runs=summary["restored_runs"],
                      restored_requests=summary["restored_requests"],
                      replayed=summary["replayed"],
                      refused=len(summary["refused"]), t=float(now))
        return summary

    def step(self) -> bool:
        """One scheduling tick: sweep SLOs (quality-floor sheds, admission
        shed/defer), admit what fits, then advance the in-flight run the
        scheduling policy selects by one unit (a plan segment / an
        adaptive step-chunk / a whole eager batch).  Returns False when
        nothing is runnable *right now* (requests may still be in flight
        toward their arrival time).  The tick is the ``serve.step`` span;
        ``serve.admit``, ``serve.launch``, ``serve.advance`` and
        ``serve.finish`` nest in it."""
        with self.tracer.span(0, "serve.step"):
            now = self.clock.now()
            with self.tracer.span(0, "serve.admit"):
                self._slo_sweep(now)
                self._admit(now)
            if not self._inflight:
                return False
            i = self.policy.select(self, now)
            fl = self._inflight[i]
            if fl.parked_by is not None:
                # a parked join target doesn't advance — its timeslice goes
                # to the chaser catching up to it
                fl = fl.parked_by
                i = self._inflight.index(fl)
            if self.resilience is None:
                self._advance(fl)
            elif self._advance_guarded(i, fl):
                return True                       # batch aborted into recovery
            if fl.rs.done:
                self._inflight.pop(i)
                with self.tracer.span(0, "serve.finish", serial=fl.serial):
                    self._finish(fl)
            else:
                if self.continuous:
                    if fl.chaser_for is not None:
                        self._try_merge(fl)
                    else:
                        self._maybe_regroup(fl)
                    self._coalesce()
                if fl in self._inflight:
                    # boundary checkpoint: the host just finished an advance
                    # (plan segment / adaptive chunk) — the only place a
                    # snapshot is ever taken, so the fused path's
                    # host_sync_count stays exactly where it was
                    self._maybe_checkpoint(fl)
                if fl in self._inflight and self.policy.rotate():
                    self._inflight.remove(fl)
                    self._inflight.append(fl)
            return True

    def run_until_drained(self) -> Dict[int, np.ndarray]:
        """Serve until every submitted request has an *outcome* — a
        result, or an explicit shed (reason in ``self.shed``/metrics) —
        sleeping the clock across arrival gaps / batching windows /
        deferral retries.  Returns {rid: latent row} for the served
        ones; use :meth:`outcome` to resolve any rid's fate."""
        stalled = 0
        last_now = None
        while True:
            if self.step():
                stalled = 0
                continue
            if len(self.queue) == 0:
                break
            now = self.clock.now()
            t = self.batcher.next_event(now)
            if t is None:
                # with a resilience policy the stall guard degrades
                # instead of dying: every stuck request becomes an
                # explicit "stalled" shed and the drain completes
                if self.resilience is not None:
                    self._stall_shed("stalled", now)
                    continue
                raise RuntimeError(
                    "serve engine stalled: queued requests but no "
                    "schedulable event")
            if t <= now:
                # wall clock crossed an arrival / batching window between
                # step()'s reading and this one — the work is formable now,
                # re-tick.  Under a frozen VirtualClock a repeat of this
                # branch with no progress means a livelock (an event that
                # never fires) — fail loudly instead of spinning forever.
                stalled = stalled + 1 if now == last_now else 0
                last_now = now
                if stalled > 64:
                    if self.resilience is not None:
                        self._stall_shed("stalled", now)
                        stalled = 0
                        continue
                    raise RuntimeError(
                        f"serve engine livelocked at t={now}: "
                        f"next_event={t} never becomes schedulable")
                continue
            last_now = now
            with self.tracer.span(0, "serve.sleep"):
                self.clock.sleep_until(t)
        return self.results

    # -- reporting -----------------------------------------------------------

    def program_budget(self) -> int:
        """Static upper bound on shape-specialized model programs this
        deployment may compile: |admissible buckets| × Σ per-entry
        program cost.  A **fused** adaptive servable costs 1 program per
        bucket (the whole candidate pool rides inside one ``lax.switch``
        program); a host-dispatched adaptive entry costs its pool size
        (2^|ever-skipped| per-signature programs); a static entry costs
        its plan's unique signatures.  Independent of the traffic
        actually served — no request mix can push compiles past it;
        entries sharing signatures only tighten it."""
        buckets = len(bucket_sizes(self.batcher.max_batch))
        pool = 0
        for name in self.store.names():
            entry = self.store.get(name)
            pool += entry.program_cost(fused=self._fused_adaptive)
        return buckets * pool

    #: executor table kinds holding *model* programs (the budgeted set;
    #: the per-shape solver-step/proxy/decide helper jits are not
    #: signature-bound)
    MODEL_PROGRAM_KINDS = ("seg", "sigstep", "eager", "fused")

    def report(self) -> Dict:
        compiles = {
            kind: self.executor.compiled_variant_count(kind)
            for kind in self.MODEL_PROGRAM_KINDS
            if self.executor.compiled_variant_count(kind)
        }
        compiles["xla_programs"] = sum(
            self.executor.xla_program_count(kind)
            for kind in self.MODEL_PROGRAM_KINDS)
        out = self.metrics.report(compile_counts=compiles,
                                  program_budget=self.program_budget())
        out["weights_cast_bytes"] = self.weights_cast_bytes
        return out
