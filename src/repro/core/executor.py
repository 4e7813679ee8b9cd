"""SmoothCache execution engine.

Runs a diffusion sampler where each step's per-type skip mask comes from a
static `Schedule`.  Because masks are static, each distinct mask compiles to
an XLA program in which skipped layers are *absent* — the FLOP savings show
up directly in ``compiled.cost_analysis()`` — and the branch cache is an
explicit pytree threaded between steps (so under pjit it inherits the
activation sharding: a cache hit also skips the layer's collectives).

Three execution paths, in order of increasing ahead-of-time analysis:

* ``sample`` — **eager**: one jitted model call per distinct skip mask,
  Python dispatch every step, every computed branch collected and merged
  into a full-structure cache.  This is the reference path (and the one
  calibration hooks into: it observes *all* branch outputs).
* ``sample_compiled`` — **segmented**: :mod:`repro.core.plan` run-length
  encodes the schedule into constant-mask segments and computes branch
  liveness; one program is compiled per *unique (mask, liveness)
  signature* (= per distinct mask, typically 2–4) and driven with a
  dynamic ``(start, length)`` trip count under ``lax.fori_loop`` (the
  dynamic-length cousin of ``lax.scan``, so segment length/position never
  triggers a recompile), with the solver state threaded through the
  carry.  Types that are never read are never collected nor resident;
  exact per-step liveness is enforced at segment boundaries by dropping
  dead entries.  Latent / solver-state / branch-cache buffers are donated
  so steady-state sampling is allocation-free.
* ``build_sampler_fn`` — **monolith**: all steps unrolled into a single
  jit-able function.  Compile time scales with step count; kept because
  ``jit(fn).lower()`` exposes whole-run FLOPs/bytes for accounting.

Classifier-free guidance doubles the batch ([cond; uncond]) exactly as in
the paper's DiT-XL protocol; the cache covers both halves.  The
unconditional half takes the model's own null condition
(``diffusion.null_cond``).  ``memory`` is an array or a text memory
``{"text", "mask"}``; either way its rows ride the run state like labels.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core import diffusion, plan as plan_lib, schedule as schedule_lib
from repro.core.solvers import Solver


def _rows_finite(x):
    """Per-sample ``isfinite`` reduction of a latent batch: ``(B,)`` bool,
    True where row ``i`` contains no NaN/Inf.  Rows of a batch never mix
    (attention is within-sample, CFG splits per sample), so this is the
    exact poisoned-sample mask — the numerical-health sentinel folded into
    the sampling carries."""
    return jnp.all(jnp.isfinite(x).reshape(x.shape[0], -1), axis=1)


def _take_rows(tree, idx, batch, axis: int = 0):
    """Slice rows ``idx`` out of every batch-shaped leaf of ``tree``
    along ``axis``: dim == ``batch`` → take those rows; == ``2*batch``
    (a CFG-doubled branch cache, ``[cond; uncond]``) → take the rows
    from both halves, keeping the halves contiguous; anything else
    passes through untouched.  Pure gathers — no model compute.
    (Branch-cache leaves carry each stage's scan-stacked repeat axis
    first, so their batch axis is 1; every other carry is batch-first.)"""
    sel = jnp.asarray(np.asarray(idx, np.int32))

    def take(leaf):
        shp = getattr(leaf, "shape", None)
        if shp is not None and len(shp) > axis:
            if shp[axis] == batch:
                return jnp.take(leaf, sel, axis=axis)
            if shp[axis] == 2 * batch:
                return jnp.concatenate(
                    [jnp.take(leaf, sel, axis=axis),
                     jnp.take(leaf, sel + batch, axis=axis)], axis=axis)
        return leaf

    return jax.tree.map(take, tree)


def _concat_rows(trees, batches, axis: int = 0):
    """Concatenate the runs' leaves along the batch ``axis`` — the merge
    dual of :func:`_take_rows`: batch-shaped leaves concat directly,
    CFG-doubled leaves concat all cond halves then all uncond halves;
    non-batch leaves are shared and the first run's value is kept."""
    def dim(leaf):
        shp = tuple(getattr(leaf, "shape", ()))
        return shp[axis] if len(shp) > axis else None

    def cat(*leaves):
        if all(dim(lf) == b for lf, b in zip(leaves, batches)):
            return jnp.concatenate(leaves, axis=axis)
        if all(dim(lf) == 2 * b for lf, b in zip(leaves, batches)):
            cond = [jnp.take(lf, jnp.arange(b), axis=axis)
                    for lf, b in zip(leaves, batches)]
            unc = [jnp.take(lf, jnp.arange(b, 2 * b), axis=axis)
                   for lf, b in zip(leaves, batches)]
            return jnp.concatenate(cond + unc, axis=axis)
        return leaves[0]

    return jax.tree.map(cat, *trees)


def _rescale_structs(structs, old_b: int, new_b: int, axis: int = 1):
    """Remap the batch (or CFG-doubled) dim of the memoized branch
    ``ShapeDtypeStruct`` tree — split/merge rebuilds the donated-buffer
    shapes without re-tracing the model.  Branch structs are stacked
    ``(repeat, batch·{1,2}, ...)``, hence the default ``axis=1``."""
    if structs is None or old_b == new_b:
        return structs

    def re(s):
        shp = list(s.shape)
        if len(shp) > axis and shp[axis] == old_b:
            shp[axis] = new_b
        elif len(shp) > axis and shp[axis] == 2 * old_b:
            shp[axis] = 2 * new_b
        return jax.ShapeDtypeStruct(tuple(shp), s.dtype)

    return jax.tree.map(re, structs)


def merge_branch_caches(cfg: ModelConfig, computed, old):
    """Fill skipped branches from the previous cache → full-structure cache
    (the eager path's collect-everything merge)."""
    out = []
    for si, st in enumerate(cfg.stages):
        stage = []
        comp_stage = computed[si] if computed is not None else None
        for bi, b in enumerate(st.unit):
            comp = comp_stage[bi] if comp_stage is not None else {}
            comp = comp or {}
            d = {}
            for name in b.branch_names():
                if name in comp and comp[name] is not None:
                    d[name] = comp[name]
                else:
                    d[name] = old[si][bi][name]
            stage.append(d)
        out.append(tuple(stage))
    return out


def empty_branch_cache(cfg: ModelConfig):
    """Structure-complete cache pytree with no resident entries."""
    return [tuple({} for _ in st.unit) for st in cfg.stages]


def pruned_branch_caches(cfg: ModelConfig, computed, old, collect, live):
    """Build a post-step cache holding only branches of ``live`` types:
    fresh outputs for ``collect`` types, passed-through entries otherwise.
    Branches outside ``live`` are dropped — with buffer donation their
    storage is reclaimed immediately."""
    collect = set(collect)
    live = set(live)
    out = []
    for si, st in enumerate(cfg.stages):
        comp_stage = computed[si] if computed is not None else None
        stage = []
        for bi, b in enumerate(st.unit):
            comp = (comp_stage[bi] or {}) if comp_stage is not None else {}
            d = {}
            for name, t in zip(b.branch_names(), b.branch_types()):
                if t not in live:
                    continue
                d[name] = comp[name] if t in collect else old[si][bi][name]
            stage.append(d)
        out.append(tuple(stage))
    return out


def prune_cache(cfg: ModelConfig, cache, live):
    """Drop every cache entry whose type is not in ``live`` — a Python-level
    pytree restructure (no device work) applied at segment boundaries."""
    live = set(live)
    out = []
    for si, st in enumerate(cfg.stages):
        stage = []
        for bi, b in enumerate(st.unit):
            types = dict(zip(b.branch_names(), b.branch_types()))
            stage.append({n: v for n, v in cache[si][bi].items()
                          if types[n] in live})
        out.append(tuple(stage))
    return out


def cache_entry_names(cfg: ModelConfig, types) -> List[tuple]:
    """(stage, block, branch_name) triples a cache restricted to ``types``
    must contain — the liveness invariant checked by the segmented loop."""
    ts = set(types)
    out = []
    for si, st in enumerate(cfg.stages):
        for bi, b in enumerate(st.unit):
            for name, t in zip(b.branch_names(), b.branch_types()):
                if t in ts:
                    out.append((si, bi, name))
    return out


@dataclasses.dataclass
class RunState:
    """In-flight state of one segmented sampling run.

    ``start_run`` creates it, ``advance_run`` consumes one plan segment per
    call (the same ops ``sample_with_plan`` performs — that loop *is*
    start + advance-until-done, so a run driven incrementally by a serving
    engine produces bit-identical latents).  With buffer donation enabled
    the previous state's device buffers are reused by the next one: hold
    only the latest ``RunState`` per run.
    """
    x: Any                                   # latent (B, ...)
    state: Any                               # solver state pytree
    cache: Any                               # branch cache (exactly live)
    kloop: Any                               # sampling-loop PRNG key
    plan: plan_lib.ExecutionPlan
    run_index: int                           # next plan.runs entry
    label: Any = None
    memory: Any = None
    structs: Any = None                      # branch ShapeDtypeStructs
    #: (B,) bool device array — per-sample numerical health, carried
    #: through the segment programs (never synced per step; read it at
    #: advance boundaries)
    healthy: Any = None

    @property
    def done(self) -> bool:
        return self.run_index >= len(self.plan.runs)

    @property
    def step(self) -> int:
        """Next sampling step to execute (== num_steps when done)."""
        if self.done:
            return self.plan.num_steps
        return self.plan.runs[self.run_index].start

    @property
    def num_steps(self) -> int:
        return self.plan.num_steps

    #: adaptive runs record realized skip sets; static runs have none
    decisions = None


@dataclasses.dataclass
class AdaptiveRunState:
    """In-flight state of one host-dispatched input-adaptive sampling run
    (per-step granularity: each ``advance_adaptive_run`` call executes one
    decision + model + solver step, exactly the ``sample_adaptive`` loop
    body).  The accumulator/lag decision state lives on device (float32 /
    int32 arrays over ``pool_types``) and is updated by the same
    :func:`~repro.core.calibration.runtime_rule` the fused program inlines;
    only the realized skip *bits* cross to the host — one small
    device→host sync per step, which is exactly what
    :meth:`SmoothCacheExecutor.sample_adaptive_fused` eliminates."""
    x: Any
    state: Any
    cache: Any
    kloop: Any
    step: int                                # next step to execute
    x_prev: Any                              # model input of previous step
    acc: Any                                 # (B, T) f32 per-row est. error
    lag: Any                                 # (B, T) i32 per-row cache age
    decisions: Tuple[tuple, ...]             # realized per-step skip sets
    schedule: Any
    tau: float
    proxy_map: Any
    by_skipset: Dict[frozenset, plan_lib.ProgramSig]
    pool_types: Tuple[str, ...]              # acc/lag/coeff row order
    coeff_a: Any                             # (T,) f32 proxy-map slopes
    coeff_b: Any                             # (T,) f32 proxy-map intercepts
    k_max: int
    label: Any = None
    memory: Any = None
    #: (B,) bool device array — per-sample numerical health (also folds
    #: in the decision accumulator's per-row finiteness)
    healthy: Any = None
    #: (B, T) bool device array — each row's DESIRED skip bits at the
    #: last decided step (None before the first τ>0 decision); the
    #: regroup signature source
    want: Any = None

    @property
    def done(self) -> bool:
        return self.step >= self.schedule.num_steps

    @property
    def num_steps(self) -> int:
        return self.schedule.num_steps

    def row_signatures(self) -> Optional[Tuple[tuple, ...]]:
        """Per-row desired skip sets at the last decided step (tuple of
        sorted type tuples, one per row) — the mask signature a serving
        engine regroups by at boundaries.  One small device→host read;
        None when no per-row decision has been taken yet."""
        if self.want is None:
            return None
        bits = np.asarray(jax.device_get(self.want))
        return tuple(plan_lib.mask_signature(self.pool_types, row)
                     for row in bits)


@dataclasses.dataclass
class FusedAdaptiveRunState:
    """In-flight state of one *fused* adaptive run: everything the
    decision rule touches — latent, previous model input, solver state,
    branch cache, accumulator/lag arrays, and the per-step decision trace
    — is a device array threaded through one donated
    ``lax.fori_loop`` program, so ``advance_adaptive_fused(n_steps)``
    executes a whole step-chunk in a single dispatch with **zero**
    per-step host syncs.  ``decisions`` materializes the trace on the
    host — call it after the run (or chunk), never per step."""
    x: Any
    x_prev: Any                              # model input of previous step
    state: Any
    cache: Any                               # pool-shared structure
    acc: Any                                 # (B, T) f32 per-row est. error
    lag: Any                                 # (B, T) i32 per-row cache age
    trace: Any                               # (S, B, T) bool per-row desires
    kloop: Any
    step: int                                # next step to execute
    schedule: Any
    tau: float
    k_max: int
    table: plan_lib.SwitchTable
    runtime: bool                            # tau > 0: on-device rule
    skip_table: Any                          # (S, T) bool static decisions
    coeff_a: Any                             # (T,) float32
    coeff_b: Any                             # (T,) float32
    label: Any = None
    memory: Any = None
    #: (B,) bool device array — per-sample numerical health, part of the
    #: fused loop carry (acc finiteness folded in), so divergence
    #: detection costs zero extra host syncs
    healthy: Any = None
    #: (S, B) float32 device array of per-row proxy signals, or None —
    #: step telemetry (``start_adaptive_fused_run(telemetry=True)``):
    #: recorded inside the fused loop carry like ``trace``, read only at
    #: the boundaries the host already syncs, so enabling it keeps
    #: ``host_sync_count`` at 0.  Step 0's value is meaningless
    #: (``x_prev`` is zeros before the first step) — report layers mask
    #: it.
    proxy_trace: Any = None

    @property
    def done(self) -> bool:
        return self.step >= self.schedule.num_steps

    @property
    def num_steps(self) -> int:
        return self.schedule.num_steps

    @property
    def pool_types(self) -> Tuple[str, ...]:
        return self.table.types

    @property
    def decisions(self) -> Tuple[tuple, ...]:
        """Realized per-step skip sets of the executed steps (tuple of
        sorted type tuples) — the AND over the trace's per-row desired
        bits, i.e. the masks the batch actually executed.  One
        device→host transfer of the packed bool trace, *not* a per-step
        sync."""
        bits = np.asarray(jax.device_get(self.trace))[:self.step]
        realized = bits.all(axis=1)                    # AND over rows
        return tuple(plan_lib.mask_signature(self.table.types, row)
                     for row in realized)

    def row_signatures(self) -> Optional[Tuple[tuple, ...]]:
        """Per-row desired skip sets at the last executed step (tuple of
        sorted type tuples, one per row) — the mask signature a serving
        engine regroups by at chunk boundaries.  One small device→host
        read of a single trace row (a boundary read, never a per-step
        sync); None before any step has executed."""
        if self.step == 0:
            return None
        bits = np.asarray(jax.device_get(self.trace[self.step - 1]))
        return tuple(plan_lib.mask_signature(self.table.types, row)
                     for row in bits)


class SmoothCacheExecutor:
    """Owns the compiled model/sampler variants (one per plan signature on
    the segmented path, one per distinct skip mask on the eager path) and
    the sampling loops."""

    def __init__(self, cfg: ModelConfig, solver: Solver, *,
                 cfg_scale: Optional[float] = None, use_flash: bool = False,
                 jit: bool = True, donate: Optional[bool] = None):
        assert cfg.task == "diffusion"
        self.cfg = cfg
        self.solver = solver
        self.cfg_scale = cfg_scale
        self.use_flash = use_flash
        self._jit = jit
        # buffer donation is a no-op (with a warning) on CPU, so default it
        # on only where XLA implements input/output aliasing
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate) and jit
        self._fns: Dict = {}
        self._plans: Dict[str, plan_lib.ExecutionPlan] = {}
        self._struct_cache: Dict = {}
        #: per-step device→host decision syncs performed by the
        #: host-dispatched adaptive loop; the fused path never increments
        #: it (asserted by tests and reported by benchmarks)
        self.host_sync_count: int = 0

    @property
    def supports_fused_adaptive(self) -> bool:
        """Whether :meth:`sample_adaptive_fused` is available: the solver
        step must run under ``lax.fori_loop`` (traced index, structure-
        stable state).  Non-scannable solvers (DPM++(3M)) fall back to the
        host-dispatched :meth:`sample_adaptive` loop."""
        return self.solver.scannable

    # -- instrumentation -----------------------------------------------------

    def fn_keys(self, kind: Optional[str] = None):
        """Keys of the compiled-variant table (regression tests assert the
        segmented path builds exactly one ``"seg"`` entry per unique plan
        signature)."""
        keys = list(self._fns)
        if kind is None:
            return keys
        return [k for k in keys
                if isinstance(k, tuple) and k and k[0] == kind]

    def compiled_variant_count(self, kind: Optional[str] = None) -> int:
        return len(self.fn_keys(kind))

    def xla_program_count(self, kind: Optional[str] = None) -> int:
        """Actual XLA executable count behind the variant table: each jitted
        entry holds one compilation per distinct input *shape* (a serving
        engine's batch-size buckets multiply here — the program-budget bound
        is |buckets| × |signatures|).  Counts one per entry in non-jit
        mode, where the entries are plain functions."""
        total = 0
        for k in self.fn_keys(kind):
            cache_size = getattr(self._fns[k], "_cache_size", None)
            total += cache_size() if cache_size is not None else 1
        return total

    # -- plan resolution -----------------------------------------------------

    def plan_for(self, schedule) -> plan_lib.ExecutionPlan:
        """Memoized liveness/segmentation analysis of a schedule."""
        ck = schedule.content_key()
        if ck not in self._plans:
            self._plans[ck] = plan_lib.analyze(schedule)
        return self._plans[ck]

    # -- model step ---------------------------------------------------------

    def _model_call(self, params, x, t, label, memory, branch_caches, *,
                    skip, collect):
        """One denoiser evaluation (CFG-doubled when configured).

        ``collect`` is ``True`` (eager/calibration: keep every branch) or a
        collection of layer types (segmented: keep only live branches)."""
        cfgm = self.cfg
        if self.cfg_scale is not None:
            x2 = jnp.concatenate([x, x], axis=0)
            t2 = jnp.concatenate([t, t], axis=0)
            lab2 = mem2 = None
            null_label, null_memory = diffusion.null_cond(cfgm, params,
                                                          label, memory)
            if label is not None:
                lab2 = jnp.concatenate([label, null_label], axis=0)
            if memory is not None:
                mem2 = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], axis=0), memory,
                    null_memory)
            pred, aux = diffusion.apply(
                cfgm, params, x2, t2, label=lab2, memory=mem2, skip=skip,
                branch_caches=branch_caches, collect_branches=collect,
                use_flash=self.use_flash)
            c, u = jnp.split(pred, 2, axis=0)
            out = u + self.cfg_scale * (c - u)
        else:
            pred, aux = diffusion.apply(
                cfgm, params, x, t, label=label, memory=memory, skip=skip,
                branch_caches=branch_caches, collect_branches=collect,
                use_flash=self.use_flash)
            out = pred
        return out, aux["branch"]

    # -- eager per-mask programs --------------------------------------------

    def _get_fn(self, mask_key, has_cache: bool):
        # the eager path always collects every computed branch (any computed
        # step may become the cache source for a later one, and calibration
        # hooks read the full tree) — so `collect` is NOT part of the key:
        # keying on it would compile the same program twice
        key = ("eager", mask_key, has_cache)
        if key in self._fns:
            return self._fns[key]
        skip = dict(mask_key)

        def fn(params, x, t, label, memory, branch_caches):
            pred, computed = self._model_call(
                params, x, t, label, memory,
                branch_caches if has_cache else None,
                skip=skip, collect=True)
            if has_cache:
                cache = merge_branch_caches(self.cfg, computed, branch_caches)
            else:
                cache = computed
            return pred, cache

        if self._jit:
            fn = jax.jit(fn)
        self._fns[key] = fn
        return fn

    def _get_plain_fn(self):
        if "plain" in self._fns:
            return self._fns["plain"]

        def fn(params, x, t, label, memory):
            pred, _ = self._model_call(params, x, t, label, memory, None,
                                       skip=None, collect=False)
            return pred

        if self._jit:
            fn = jax.jit(fn)
        self._fns["plain"] = fn
        return fn

    # -- segmented per-signature programs -----------------------------------

    def _sig_step(self, params, x, t, label, memory, cache, *, skip, collect,
                  live):
        """One plan-driven model evaluation + liveness-pruned cache update:
        skipped branches read the cache, ``collect`` types write fresh
        outputs, and only ``live`` types appear in the output cache."""
        pred, computed = self._model_call(
            params, x, t, label, memory,
            cache if any(skip.values()) else None,
            skip=skip, collect=frozenset(collect))
        new_cache = pruned_branch_caches(self.cfg, computed, cache,
                                         collect, live)
        return pred, new_cache

    def _get_sig_loop_fn(self, sig: plan_lib.ProgramSig):
        """Fused segment program for one signature: model + solver step
        under ``lax.fori_loop`` over a dynamic ``[start, start+length)``
        step range, so a single compilation serves every segment of this
        mask regardless of length or position.  The signature's canonical
        collect set makes the cache pytree a loop invariant (skipped types
        pass through, collected types are overwritten each iteration).
        Latent, solver state, and cache buffers are donated — steady-state
        segments run allocation-free."""
        key = ("seg", sig)
        if key in self._fns:
            return self._fns[key]
        solver = self.solver
        skip, collect, live = sig.skip, sig.collect, sig.structure

        def fn(params, x, state, cache, healthy, start, length, kloop,
               label, memory):
            def body(i, carry):
                x, state, cache, healthy = carry
                t = jnp.full((x.shape[0],), solver.model_times[i])
                pred, cache = self._sig_step(params, x, t, label, memory,
                                             cache, skip=skip,
                                             collect=collect, live=live)
                kstep = (jax.random.fold_in(kloop, i)
                         if solver.stochastic else None)
                x, state = solver.step(x, pred, i, state, kstep)
                # health sentinel rides the carry — no host traffic
                healthy = healthy & _rows_finite(x)
                return (x, state, cache, healthy)

            return jax.lax.fori_loop(start, start + length, body,
                                     (x, state, cache, healthy))

        if self._jit:
            donate = (1, 2, 3, 4) if self._donate else ()
            fn = jax.jit(fn, donate_argnums=donate)
        self._fns[key] = fn
        return fn

    def _get_sig_model_fn(self, sig: plan_lib.ProgramSig):
        """Model-only signature program for non-scannable solvers (e.g.
        DPM++(3M): Python control flow on the step index / state structure).
        The solver step runs eagerly between calls; the cache is donated."""
        key = ("sigstep", sig)
        if key in self._fns:
            return self._fns[key]
        skip, collect, live = sig.skip, sig.collect, sig.structure

        def fn(params, x, t, label, memory, cache):
            return self._sig_step(params, x, t, label, memory, cache,
                                  skip=skip, collect=collect, live=live)

        if self._jit:
            donate = (5,) if self._donate else ()
            fn = jax.jit(fn, donate_argnums=donate)
        self._fns[key] = fn
        return fn

    def _branch_structs(self, params, x, label, memory):
        """ShapeDtypeStructs of every branch-cache entry (one abstract
        trace, memoized per latent shape) — used to build the donated
        placeholder buffers a segment's collect entries start from."""
        key = (x.shape, str(x.dtype), label is not None, memory is not None)
        if key in self._struct_cache:
            return self._struct_cache[key]
        t = jax.ShapeDtypeStruct((x.shape[0],), jnp.float32)
        structs = jax.eval_shape(
            lambda p, xx, tt, lab, mem: self._model_call(
                p, xx, tt, lab, mem, None, skip=None, collect=True)[1],
            params, x, t, label, memory)
        self._struct_cache[key] = structs
        return structs

    def _enter_run_cache(self, cache, sig: plan_lib.ProgramSig, structs):
        """Restructure the (exactly-live) boundary cache into the run's
        loop-invariant structure: pass through the entries the mask reads,
        and add placeholder buffers for the collect entries (their input
        values are never read — the program overwrites them on the first
        iteration, and donation recycles the allocation)."""
        live_in = set(sig.live_in)
        collect = set(sig.collect)
        out = []
        for si, st in enumerate(self.cfg.stages):
            stage = []
            for bi, b in enumerate(st.unit):
                d = {}
                for name, t in zip(b.branch_names(), b.branch_types()):
                    if t in live_in:
                        d[name] = cache[si][bi][name]
                    elif t in collect:
                        s = structs[si][bi][name]
                        d[name] = jnp.zeros(s.shape, s.dtype)
                stage.append(d)
            out.append(tuple(stage))
        return out

    def _get_solver_step(self):
        """Solver step used by the eager loops.  For scannable solvers it is
        jitted with a *traced* step index — the same numeric class XLA uses
        inside the segmented path's fused loop programs (traced-index jit,
        ``fori_loop``, and fused model+solver programs produce identical
        bits; op-by-op eager execution and static-index constant folding do
        not), so eager and segmented sampling stay bit-identical.
        Non-scannable solvers run op-by-op on every path — also
        self-consistent."""
        if "solver_step" in self._fns:
            return self._fns["solver_step"]
        solver = self.solver
        if solver.scannable and self._jit:
            fn = jax.jit(lambda x, pred, s, state, key:
                         solver.step(x, pred, s, state, key))
        else:
            fn = solver.step
        self._fns["solver_step"] = fn
        return fn

    def _get_proxy_fn(self):
        """Relative-L1 change between consecutive model inputs — the
        adaptive path's per-step decision scalar (one reduction over the
        latent, computed before the model call it gates).  The formula is
        shared with calibration (``calibration.rel_l1_change``) so the
        fitted proxy→error maps stay valid at runtime."""
        if "proxy" in self._fns:
            return self._fns["proxy"]
        from repro.core import calibration  # late: calibration is np-heavy
        fn = calibration.rel_l1_change
        if self._jit:
            fn = jax.jit(fn)
        self._fns["proxy"] = fn
        return fn

    def _get_decide_fn(self):
        """One jitted evaluation of the adaptive reuse rule for the
        host-dispatched loop: per-row proxy reduction +
        ``calibration.batch_rule`` — the *same* float32 arithmetic the
        fused program inlines into its loop body, so host and fused
        decision sequences agree bit-for-bit.  Returns ``(want, realized,
        acc', lag')`` with per-sample ``(B, T)`` accumulator state; only
        the realized bits are pulled to the host (the per-step sync the
        fused path removes)."""
        if "decide" in self._fns:
            return self._fns["decide"]
        from repro.core import calibration

        def fn(x, x_prev, acc, lag, a, b, tau, k_max):
            proxy_rows = calibration.rel_l1_change_rows(x, x_prev)
            return calibration.batch_rule(proxy_rows, acc, lag, a, b, tau,
                                          k_max)

        if self._jit:
            fn = jax.jit(fn)
        self._fns["decide"] = fn
        return fn

    def _get_health_fn(self):
        """Boundary health update for the paths whose loop body is not one
        fused program (non-scannable segments, host-dispatched adaptive
        steps): fold the latent's per-row finiteness — and the decision
        accumulator's, when there is one — into the carried flags.  Stays
        on device; nothing syncs here.  (Not a model program: excluded
        from ``MODEL_PROGRAM_KINDS`` and the compile budget.)"""
        if "health" in self._fns:
            return self._fns["health"]

        def fn(healthy, x, acc):
            # acc is per-sample (B, T): a poisoned accumulator row flips
            # only its own flag ((0,)-shaped dummy reduces to scalar True)
            return (healthy & _rows_finite(x)
                    & jnp.all(jnp.isfinite(acc), axis=-1))

        if self._jit:
            fn = jax.jit(fn)
        self._fns["health"] = fn
        return fn

    # -- fused adaptive program ---------------------------------------------

    def _get_fused_fn(self, table: plan_lib.SwitchTable, runtime: bool,
                      telemetry: bool = False):
        """The whole adaptive sampling loop as ONE donated program: proxy
        computation, ``runtime_rule`` over stacked proxy-map coefficients,
        accumulator/lag state carried as device arrays, ``lax.switch``
        over the pool's branch programs (every pool signature shares one
        cache structure, so the carry is uniform by construction), the
        solver step, and a packed bool decision trace — under a
        ``lax.fori_loop`` with a dynamic ``[start, start+length)`` range,
        so one compilation per (batch-shape, pool) signature serves every
        chunk size a serving engine timeslices with.  No value ever
        crosses to the host inside the loop.

        ``runtime=False`` (τ=0) replaces the rule with a lookup into the
        static schedule's precomputed ``skip_table`` — same program
        structure, bit-identical to ``sample_compiled``.

        ``telemetry=True`` additionally records the per-row proxy signal
        into a ``(S, B)`` carry array each step (computed even under
        ``runtime=False``, where the rule itself never reads it).  The
        flag is part of the memo key, so telemetry runs compile their own
        program and non-telemetry programs are untouched; the latent
        arithmetic is identical either way (asserted bit-for-bit by the
        obs bench)."""
        key = ("fused", table, runtime, telemetry)
        if key in self._fns:
            return self._fns[key]
        if not self.solver.scannable:
            raise ValueError(
                f"solver {self.solver.name!r} is not scannable; the fused "
                "adaptive path needs the solver step inside lax.fori_loop "
                "— use sample_adaptive (host dispatch) instead")
        from repro.core import calibration
        solver = self.solver
        types = table.types
        n_types = len(types)
        weights = jnp.asarray([1 << i for i in range(n_types)], jnp.int32)

        def fn(params, x, x_prev, state, cache, acc, lag, trace, healthy,
               proxy_trace, start, length, kloop, label, memory, a, b,
               tau, k_max, skip_table):
            def make_branch(sig):
                def branch(bx, bt, bcache):
                    return self._sig_step(params, bx, bt, label, memory,
                                          bcache, skip=sig.skip,
                                          collect=sig.collect, live=types)
                return branch

            branches = [make_branch(sig) for sig in table.branches]

            def body(s, carry):
                x, x_prev, state, cache, acc, lag, trace, healthy, \
                    proxy_trace = carry
                proxy_rows = None
                if runtime or telemetry:
                    proxy_rows = calibration.rel_l1_change_rows(x, x_prev)
                if runtime:
                    # per-sample rule: each row wants its own skip set from
                    # its own (B, T) acc/lag state; the batch realizes the
                    # AND (one compute refreshes every row's cache)
                    want, bits, acc, lag = calibration.batch_rule(
                        proxy_rows, acc, lag, a, b, tau, k_max,
                        force_compute=(s == 0))
                else:
                    bits = skip_table[s]
                    want = jnp.broadcast_to(bits, acc.shape)
                if telemetry:
                    # step telemetry rides the same carry as the decision
                    # trace: recorded on device, read only at boundaries
                    proxy_trace = proxy_trace.at[s].set(proxy_rows)
                code = (jnp.sum(bits.astype(jnp.int32) * weights)
                        if n_types else jnp.int32(0))
                t = jnp.full((x.shape[0],), solver.model_times[s])
                pred, cache = jax.lax.switch(code, branches, x, t, cache)
                kstep = (jax.random.fold_in(kloop, s)
                         if solver.stochastic else None)
                x_next, state = solver.step(x, pred, s, state, kstep)
                # the trace records per-row DESIRED bits (S, B, T): the
                # executed mask is their AND, and the rows are the regroup
                # signature a serving engine reads at chunk boundaries
                trace = trace.at[s].set(want)
                # health sentinel in the carry: poisoned latents and a
                # runaway/NaN accumulator both flip (only) their row's
                # flag — still zero host syncs inside the loop
                healthy = (healthy & _rows_finite(x_next)
                           & jnp.all(jnp.isfinite(acc), axis=-1))
                return (x_next, x, state, cache, acc, lag, trace, healthy,
                        proxy_trace)

            return jax.lax.fori_loop(
                start, start + length, body,
                (x, x_prev, state, cache, acc, lag, trace, healthy,
                 proxy_trace))

        if self._jit:
            # donate everything the successor state replaces; kloop /
            # label / memory / coefficients are reused across chunks
            donate = (1, 2, 3, 4, 5, 6, 7, 8, 9) if self._donate else ()
            fn = jax.jit(fn, donate_argnums=donate)
        self._fns[key] = fn
        return fn

    # -- sampling loops ------------------------------------------------------

    def latent_batch_shape(self, batch):
        return (batch,) + tuple(self.cfg.latent_shape)

    def initial_latent(self, key, batch: int):
        """The noise-init convention shared by every sampling path:
        ``(x_init, loop_key)`` from one key split.  Calibration uses it to
        reconstruct the model-input trajectory for the proxy signal."""
        knoise, kloop = jax.random.split(key)
        return jax.random.normal(knoise, self.latent_batch_shape(batch)), kloop

    def initial_latent_rows(self, keys, batch: Optional[int] = None):
        """Per-row noise init: row ``i`` is exactly the batch-1
        :meth:`initial_latent` draw of ``keys[i]``, so ANY grouping of the
        rows — one big batch, singletons, or any split/merge in between —
        samples each row bit-identically to its own solo run (XLA keeps
        independent rows bitwise stable across batch shapes; the
        continuous-batching determinism contract rests on this).  The loop
        key is derived from ``keys[0]``; deterministic solvers never read
        it, and stochastic solvers are rejected because their loop-key
        noise IS batch-shape-dependent."""
        keys = list(keys)
        if batch is not None and int(batch) != len(keys):
            raise ValueError(f"row_keys has {len(keys)} entries for "
                             f"batch {batch}")
        if not keys:
            raise ValueError("row_keys must be non-empty")
        if self.solver.stochastic:
            raise ValueError(
                f"solver {self.solver.name!r} is stochastic: its loop-key "
                "noise depends on the batch shape, so per-row keys cannot "
                "make rows batch-invariant — use a single batch key")
        rows, kloop = [], None
        for k in keys:
            x1, kl = self.initial_latent(k, 1)
            if kloop is None:
                kloop = kl
            rows.append(x1)
        return jnp.concatenate(rows, axis=0), kloop

    def sample(self, params, key, batch: int, *, schedule=None, label=None,
               memory=None, collect_hook: Optional[Callable] = None,
               return_trajectory: bool = False):
        """Eager reference sampler.  ``schedule=None`` → no caching."""
        cfgm = self.cfg
        s_total = self.solver.num_steps
        if schedule is None:
            types = cfgm.layer_types()
            schedule = schedule_lib.no_cache(types, s_total)
        assert schedule.num_steps == s_total
        x, kloop = self.initial_latent(key, batch)
        state = self.solver.init_state()
        solver_step = self._get_solver_step()
        cache = None
        traj = []
        caching_active = (collect_hook is not None or
                          any(v.any() for v in schedule.skip.values()))
        if not caching_active:
            # fast path: plain sampling, no branch collection
            fn = self._get_plain_fn()
            for s in range(s_total):
                t = jnp.full((batch,), self.solver.model_times[s])
                pred = fn(params, x, t, label, memory)
                x, state = solver_step(x, pred, s, state,
                                       jax.random.fold_in(kloop, s))
                if return_trajectory:
                    traj.append(x)
            return (x, traj) if return_trajectory else x
        for s in range(s_total):
            mask_key = schedule.mask_key_at(s)
            t = jnp.full((batch,), self.solver.model_times[s])
            fn = self._get_fn(mask_key, has_cache=cache is not None)
            pred, cache = fn(params, x, t, label, memory, cache)
            if collect_hook is not None:
                collect_hook(s, cache)
            kstep = jax.random.fold_in(kloop, s)
            x, state = solver_step(x, pred, s, state, kstep)
            if return_trajectory:
                traj.append(x)
        return (x, traj) if return_trajectory else x

    def start_run(self, params, key, batch: int, *,
                  plan: plan_lib.ExecutionPlan, schedule=None, label=None,
                  memory=None, row_keys=None) -> RunState:
        """Begin a resumable segmented run: validate the plan, draw the
        initial latent, and return a :class:`RunState` positioned before
        the first segment.  Drive it with :meth:`advance_run` — a serving
        engine interleaves several in-flight states this way, and
        ``start + advance-until-done`` is exactly ``sample_with_plan``.

        ``row_keys`` (one PRNG key per row, replaces ``key``) draws each
        row via :meth:`initial_latent_rows`, making the run divisible:
        any :meth:`split_run` / :meth:`merge_runs` regrouping of its rows
        stays bit-identical per row to the rows' solo runs."""
        if plan.num_steps != self.solver.num_steps:
            raise ValueError(f"plan has {plan.num_steps} steps, solver "
                             f"{self.solver.num_steps}")
        if (schedule is not None and plan.schedule_fingerprint is not None
                and plan.schedule_fingerprint
                != plan_lib.schedule_fingerprint(schedule)):
            raise ValueError("plan was analyzed from a different schedule "
                             "(fingerprint mismatch) — re-run plan_for()")
        if row_keys is not None:
            x, kloop = self.initial_latent_rows(row_keys, batch)
        else:
            x, kloop = self.initial_latent(key, batch)
        return RunState(
            x=x, state=self.solver.init_state(),
            cache=empty_branch_cache(self.cfg), kloop=kloop, plan=plan,
            run_index=0, label=label, memory=memory,
            structs=self._branch_structs(params, x, label, memory),
            healthy=jnp.ones((batch,), jnp.bool_))

    def advance_run(self, params, rs: RunState, *,
                    check: bool = False) -> RunState:
        """Advance an in-flight run by one plan segment: enter the
        signature's loop-invariant cache structure, execute the segment's
        steps (fused ``fori_loop`` program, or per-step model programs +
        eager solver for non-scannable solvers), and enforce exact liveness
        at the boundary.  Returns the successor state; with donation the
        input state's buffers are recycled — drop it."""
        if rs.done:
            raise ValueError("run is already complete")
        run = rs.plan.runs[rs.run_index]
        x, state, kloop = rs.x, rs.state, rs.kloop
        label, memory = rs.label, rs.memory
        healthy = rs.healthy
        if healthy is None:                  # pre-sentinel state: assume ok
            healthy = jnp.ones((x.shape[0],), jnp.bool_)
        cache = self._enter_run_cache(rs.cache, run.sig, rs.structs)
        if self.solver.scannable:
            fn = self._get_sig_loop_fn(run.sig)
            x, state, cache, healthy = fn(params, x, state, cache, healthy,
                                          run.start, run.length, kloop,
                                          label, memory)
        else:
            solver_step = self._get_solver_step()
            fn = self._get_sig_model_fn(run.sig)
            for s in range(run.start, run.start + run.length):
                t = jnp.full((x.shape[0],), self.solver.model_times[s])
                pred, cache = fn(params, x, t, label, memory, cache)
                x, state = solver_step(x, pred, s, state,
                                       jax.random.fold_in(kloop, s))
            # NaN/Inf persists in the latent through solver steps, so one
            # boundary check catches any step of the segment (on device,
            # no sync)
            healthy = self._get_health_fn()(healthy, x,
                                            jnp.zeros((0,), jnp.float32))
        # exact liveness at the boundary: entries the next segment does
        # not read are dead — drop them (free: a Python restructure;
        # donation already recycled their buffers)
        cache = prune_cache(self.cfg, cache, run.live_out)
        if check:
            expect = set(cache_entry_names(self.cfg, run.live_out))
            got = {(si, bi, name)
                   for si, stage in enumerate(cache)
                   for bi, d in enumerate(stage)
                   for name in d}
            assert got == expect, (
                f"liveness violation after steps "
                f"[{run.start}, {run.start + run.length}): resident "
                f"{sorted(got)} != live {sorted(expect)}")
        return dataclasses.replace(rs, x=x, state=state, cache=cache,
                                   run_index=rs.run_index + 1,
                                   healthy=healthy)

    def sample_with_plan(self, params, key, batch: int, *,
                         plan: plan_lib.ExecutionPlan, schedule=None,
                         label=None, memory=None, check: bool = False):
        """Segmented sampler: Python dispatch per *segment* (not per step),
        one compiled program per unique plan signature.

        ``check=True`` verifies after every segment that the resident cache
        pytree holds exactly the plan's live entries (the liveness
        invariant: dead branches are provably absent)."""
        rs = self.start_run(params, key, batch, plan=plan, schedule=schedule,
                            label=label, memory=memory)
        while not rs.done:
            rs = self.advance_run(params, rs, check=check)
        return rs.x

    def sample_compiled(self, params, key, batch: int, *, schedule=None,
                        label=None, memory=None, plan=None,
                        check: bool = False):
        """Segmented-plan sampler (the serving hot path): analyzes the
        schedule (memoized, or pass a pre-analyzed ``plan`` from a
        :class:`~repro.cache.artifact.CacheArtifact`) and compiles one
        program per unique (mask, liveness) signature — not per step, not
        one monolith."""
        if schedule is None:
            schedule = schedule_lib.no_cache(self.cfg.layer_types(),
                                             self.solver.num_steps)
        if plan is None:
            plan = self.plan_for(schedule)
        return self.sample_with_plan(params, key, batch, plan=plan,
                                     schedule=schedule, label=label,
                                     memory=memory, check=check)

    # -- input-adaptive runtime dispatch ------------------------------------

    def sample_adaptive(self, params, key, batch: int, *, schedule,
                        tau: float, proxy_map=None, pool=None, k_max: int = 3,
                        label=None, memory=None,
                        return_decisions: bool = False):
        """Input-adaptive sampler: per-step reuse decisions dispatched over
        the precompiled mask-lattice pool.

        ``schedule`` is the offline (static) base schedule: it defines the
        candidate pool (:func:`repro.core.plan.mask_lattice` over its
        ever-skipped types) and is followed verbatim when ``tau == 0``.
        With ``tau > 0`` the runtime rule takes over: before each model
        call the proxy signal (relative L1 change of the latent) is mapped
        through the calibrated ``proxy_map`` to a per-type error estimate;
        a type is reused while the error accumulated since its last compute
        stays under ``tau`` and the cache age stays ≤ ``k_max``, and is
        recomputed (resetting the accumulator) otherwise.

        Every decision selects a signature from the pool, so at most
        ``len(pool)`` programs are ever compiled (2^|ever-skipped|,
        typically 4) — never one per step.  All pool signatures share one
        cache structure (the ever-skipped type set), so per-step dispatch
        needs no cache restructuring; the per-signature programs are the
        same ``"sigstep"`` table entries the non-scannable segmented path
        uses, and the solver step runs through the same traced-index jit as
        the eager path, so ``tau=0`` reproduces ``sample_compiled`` on the
        same schedule bit-identically.

        ``return_decisions=True`` additionally returns the realized
        per-step skip sets (tuple of sorted type tuples) for accounting.
        """
        rs = self.start_adaptive_run(
            params, key, batch, schedule=schedule, tau=tau,
            proxy_map=proxy_map, pool=pool, k_max=k_max, label=label,
            memory=memory)
        while not rs.done:
            rs = self.advance_adaptive_run(params, rs)
        if return_decisions:
            return rs.x, rs.decisions
        return rs.x

    def _adaptive_setup(self, schedule, tau, proxy_map, pool, k_max):
        """Shared validation + pool derivation for both adaptive paths.
        Returns ``(schedule, tau, pool, by_skipset, pool_types,
        coeff_a, coeff_b)`` with the proxy-map coefficients stacked into
        the device representation (zeros when τ=0 never evaluates them)."""
        s_total = self.solver.num_steps
        if schedule is None:
            schedule = schedule_lib.no_cache(self.cfg.layer_types(), s_total)
        if schedule.num_steps != s_total:
            raise ValueError(f"schedule has {schedule.num_steps} steps, "
                             f"solver {s_total}")
        tau = float(tau)
        if tau < 0:
            raise ValueError(f"tau must be >= 0, got {tau}")
        if int(k_max) < 1:
            raise ValueError(
                f"adaptive k_max must be >= 1, got {k_max} — k_max=0 "
                "would compile the whole candidate pool yet never reuse "
                "a cache entry (silently behaving like no_cache)")
        if tau > 0 and proxy_map is None:
            raise ValueError(
                "sample_adaptive with tau > 0 needs a calibrated proxy_map "
                "(calibrate the adaptive policy or load its artifact)")
        if pool is None:
            pool = plan_lib.mask_lattice(schedule)
        by_skipset = plan_lib.pool_index(pool)
        pool_live = frozenset().union(*by_skipset) if by_skipset else \
            frozenset()
        pool_types = tuple(sorted(pool_live))
        if tau > 0:
            try:
                a, b = proxy_map.stacked(pool_types)
            except KeyError as e:
                # keep the adaptive misconfiguration contract: every
                # invalid-parameter path out of here is a ValueError
                raise ValueError(f"proxy_map lacks coefficients for the "
                                 f"candidate pool — recalibrate: {e}")
            coeff_a, coeff_b = jnp.asarray(a), jnp.asarray(b)
        else:
            zeros = np.zeros((len(pool_types),), np.float32)
            coeff_a = coeff_b = jnp.asarray(zeros)
        return schedule, tau, pool, by_skipset, pool_types, coeff_a, coeff_b

    def start_adaptive_run(self, params, key, batch: int, *, schedule,
                           tau: float, proxy_map=None, pool=None,
                           k_max: int = 3, label=None,
                           memory=None, row_keys=None) -> AdaptiveRunState:
        """Begin a resumable host-dispatched adaptive run: validate the
        decision parameters, derive/index the candidate pool, and enter the
        pool's shared cache structure.  Drive it with
        :meth:`advance_adaptive_run` (one step per call);
        ``start + advance-until-done`` is exactly :meth:`sample_adaptive`.
        ``row_keys`` draws per-row initial latents (see :meth:`start_run`)
        so the run can be split/merged bit-identically per row."""
        schedule, tau, pool, by_skipset, pool_types, coeff_a, coeff_b = \
            self._adaptive_setup(schedule, tau, proxy_map, pool, k_max)
        n_types = len(pool_types)
        if row_keys is not None:
            x, kloop = self.initial_latent_rows(row_keys, batch)
        else:
            x, kloop = self.initial_latent(key, batch)
        structs = self._branch_structs(params, x, label, memory)
        # every pool signature shares the same structure; enter once with
        # placeholder buffers for all ever-skipped types
        cache = self._enter_run_cache(empty_branch_cache(self.cfg),
                                      by_skipset[frozenset()], structs)
        return AdaptiveRunState(
            x=x, state=self.solver.init_state(), cache=cache, kloop=kloop,
            step=0, x_prev=None,
            acc=jnp.zeros((batch, n_types), jnp.float32),
            lag=jnp.zeros((batch, n_types), jnp.int32),
            decisions=(), schedule=schedule, tau=tau, proxy_map=proxy_map,
            by_skipset=by_skipset, pool_types=pool_types,
            coeff_a=coeff_a, coeff_b=coeff_b, k_max=int(k_max),
            label=label, memory=memory,
            healthy=jnp.ones((batch,), jnp.bool_))

    def advance_adaptive_run(self, params,
                             rs: AdaptiveRunState) -> AdaptiveRunState:
        """Advance an in-flight adaptive run by one step: evaluate the
        decision rule on device (shared with the fused path), pull the
        skip *bits* to the host — the one per-step sync this path pays —
        dispatch the matching precompiled pool program, and run the solver
        step.  Returns the successor state; with donation the input
        state's cache buffers are recycled — drop it."""
        if rs.done:
            raise ValueError("run is already complete")
        s = rs.step
        x, schedule, tau = rs.x, rs.schedule, rs.tau
        acc, lag, want = rs.acc, rs.lag, rs.want
        if s == 0:
            skipset = frozenset()           # cache is empty: compute all
        elif tau == 0.0:
            # trust the offline schedule verbatim (bit-identical to
            # sample_compiled on the same schedule)
            skipset = frozenset(t for t, sk in schedule.mask_key_at(s)
                                if sk)
        else:
            want, realized_dev, acc, lag = self._get_decide_fn()(
                x, rs.x_prev, rs.acc, rs.lag, rs.coeff_a, rs.coeff_b,
                tau, rs.k_max)
            bits = np.asarray(jax.device_get(realized_dev))
            self.host_sync_count += 1       # the per-step device→host sync
            skipset = frozenset(t for t, hit in zip(rs.pool_types, bits)
                                if hit)
        sig = rs.by_skipset.get(skipset)
        if sig is None:
            raise ValueError(
                f"static schedule mask at step {s} skips "
                f"{sorted(skipset)}, absent from the candidate pool — "
                "derive the pool from this schedule via mask_lattice()")
        t_arr = jnp.full((x.shape[0],), self.solver.model_times[s])
        fn = self._get_sig_model_fn(sig)
        pred, cache = fn(params, x, t_arr, rs.label, rs.memory, rs.cache)
        x_next, state = self._get_solver_step()(
            x, pred, s, rs.state, jax.random.fold_in(rs.kloop, s))
        healthy = rs.healthy
        if healthy is None:                  # pre-sentinel state: assume ok
            healthy = jnp.ones((x.shape[0],), jnp.bool_)
        # on-device fold — does NOT join the per-step decision sync above
        healthy = self._get_health_fn()(healthy, x_next, acc)
        return dataclasses.replace(
            rs, x=x_next, state=state, cache=cache, step=s + 1, x_prev=x,
            acc=acc, lag=lag, want=want, healthy=healthy,
            decisions=rs.decisions + (tuple(sorted(skipset)),))

    # -- fused adaptive sampling (decision + dispatch on device) -------------

    def sample_adaptive_fused(self, params, key, batch: int, *, schedule,
                              tau: float, proxy_map=None, pool=None,
                              k_max: int = 3, label=None, memory=None,
                              return_decisions: bool = False):
        """Input-adaptive sampler fused into a single donated program:
        the entire loop — proxy computation, ``runtime_rule`` over the
        proxy map's stacked coefficients, accumulator/lag carry, and
        ``lax.switch`` dispatch over the pool's branch programs — runs on
        device, with **zero** per-step host syncs and exactly one
        compiled program per (batch-shape, pool) signature (vs pool-size
        programs × per-step dispatches on :meth:`sample_adaptive`).

        Decision sequences are bit-identical to :meth:`sample_adaptive`
        (both evaluate :func:`~repro.core.calibration.runtime_rule` in
        float32 on device), and at ``tau=0`` the whole run is
        bit-identical to :meth:`sample_compiled` on the same schedule.
        Requires a scannable solver — see :attr:`supports_fused_adaptive`.

        ``return_decisions=True`` additionally returns the realized
        per-step skip sets, materialized from the device-side decision
        trace after the run (one transfer, not per step)."""
        rs = self.start_adaptive_fused_run(
            params, key, batch, schedule=schedule, tau=tau,
            proxy_map=proxy_map, pool=pool, k_max=k_max, label=label,
            memory=memory)
        rs = self.advance_adaptive_fused(params, rs)
        if return_decisions:
            return rs.x, rs.decisions
        return rs.x

    def _fused_setup(self, schedule, tau, proxy_map, pool, k_max):
        """Shared derivation for the fused start + snapshot-import paths:
        validates the solver, runs :meth:`_adaptive_setup`, builds the
        ``lax.switch`` branch table, and materializes the static
        ``skip_table`` (τ=0) or its shape-stable runtime dummy (τ>0).
        Returns ``(schedule, tau, table, runtime, skip_table, coeff_a,
        coeff_b)`` — all deterministic functions of the entry parameters,
        which is what makes a restored run's continuation bit-identical
        to the original's."""
        if not self.supports_fused_adaptive:
            raise ValueError(
                f"solver {self.solver.name!r} is not scannable; the fused "
                "adaptive path needs the solver step inside lax.fori_loop "
                "— use sample_adaptive (host dispatch) instead")
        schedule, tau, pool, by_skipset, pool_types, coeff_a, coeff_b = \
            self._adaptive_setup(schedule, tau, proxy_map, pool, k_max)
        table = plan_lib.switch_branch_table(pool)
        s_total = schedule.num_steps
        n_types = len(table.types)
        runtime = tau > 0
        if runtime:
            # the rule only ever selects subsets of the pool types; the
            # static table is never read — pass a shape-stable dummy
            skip_table = jnp.zeros((1, n_types), jnp.bool_)
        else:
            cols = [np.asarray(schedule.skip[t], bool) for t in table.types]
            skip_table = (np.stack(cols, axis=1) if cols
                          else np.zeros((s_total, 0), bool))
            for s in range(s_total):
                skipset = frozenset(t for t, sk in schedule.mask_key_at(s)
                                    if sk)
                if skipset not in by_skipset:
                    raise ValueError(
                        f"static schedule mask at step {s} skips "
                        f"{sorted(skipset)}, absent from the candidate "
                        "pool — derive the pool from this schedule via "
                        "mask_lattice()")
            skip_table = jnp.asarray(skip_table)
        return schedule, tau, table, runtime, skip_table, coeff_a, coeff_b

    def start_adaptive_fused_run(self, params, key, batch: int, *,
                                 schedule, tau: float, proxy_map=None,
                                 pool=None, k_max: int = 3, label=None,
                                 memory=None, row_keys=None,
                                 telemetry: bool = False
                                 ) -> FusedAdaptiveRunState:
        """Begin a resumable fused adaptive run.  Drive it with
        :meth:`advance_adaptive_fused` — a serving engine timeslices with
        ``n_steps`` chunks, each a single program dispatch.  ``row_keys``
        draws per-row initial latents (see :meth:`start_run`) so the run
        can be split/merged bit-identically per row.  ``telemetry=True``
        additionally records the per-row proxy signal into the loop carry
        (``rs.proxy_trace``) for per-request
        :class:`repro.obs.CacheReport` explainers — still zero per-step
        host syncs, and the latent bits are unchanged (the telemetry
        program differs only in the extra carry writes)."""
        schedule, tau, table, runtime, skip_table, coeff_a, coeff_b = \
            self._fused_setup(schedule, tau, proxy_map, pool, k_max)
        s_total = schedule.num_steps
        n_types = len(table.types)
        if row_keys is not None:
            x, kloop = self.initial_latent_rows(row_keys, batch)
        else:
            x, kloop = self.initial_latent(key, batch)
        structs = self._branch_structs(params, x, label, memory)
        cache = self._enter_run_cache(empty_branch_cache(self.cfg),
                                      table.branches[0], structs)
        return FusedAdaptiveRunState(
            x=x, x_prev=jnp.zeros_like(x), state=self.solver.init_state(),
            cache=cache,
            acc=jnp.zeros((batch, n_types), jnp.float32),
            lag=jnp.zeros((batch, n_types), jnp.int32),
            trace=jnp.zeros((s_total, batch, n_types), jnp.bool_),
            kloop=kloop, step=0, schedule=schedule, tau=tau,
            k_max=int(k_max), table=table, runtime=runtime,
            skip_table=skip_table, coeff_a=coeff_a, coeff_b=coeff_b,
            label=label, memory=memory,
            healthy=jnp.ones((batch,), jnp.bool_),
            proxy_trace=(jnp.zeros((s_total, batch), jnp.float32)
                         if telemetry else None))

    def advance_adaptive_fused(self, params, rs: FusedAdaptiveRunState,
                               n_steps: Optional[int] = None
                               ) -> FusedAdaptiveRunState:
        """Advance an in-flight fused run by ``n_steps`` sampling steps
        (default: all remaining) in ONE program dispatch — the dynamic
        ``(start, length)`` trip count means chunk size never triggers a
        recompile, so a serving engine can timeslice adaptive runs
        without per-step host round-trips.  Returns the successor state;
        with donation the input state's buffers are recycled — drop it."""
        if rs.done:
            raise ValueError("run is already complete")
        remaining = rs.num_steps - rs.step
        length = remaining if n_steps is None else min(int(n_steps),
                                                       remaining)
        if length < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        telemetry = rs.proxy_trace is not None
        fn = self._get_fused_fn(rs.table, rs.runtime, telemetry)
        healthy = rs.healthy
        if healthy is None:                  # pre-sentinel state: assume ok
            healthy = jnp.ones((rs.x.shape[0],), jnp.bool_)
        # telemetry-off runs carry a shape-stable dummy through the loop
        # (the program never touches it; the memo key separates variants)
        proxy_trace = (rs.proxy_trace if telemetry
                       else jnp.zeros((0, 0), jnp.float32))
        x, x_prev, state, cache, acc, lag, trace, healthy, proxy_trace = \
            fn(params, rs.x, rs.x_prev, rs.state, rs.cache, rs.acc,
               rs.lag, rs.trace, healthy, proxy_trace, rs.step, length,
               rs.kloop, rs.label, rs.memory, rs.coeff_a, rs.coeff_b,
               rs.tau, rs.k_max, rs.skip_table)
        return dataclasses.replace(
            rs, x=x, x_prev=x_prev, state=state, cache=cache, acc=acc,
            lag=lag, trace=trace, step=rs.step + length, healthy=healthy,
            proxy_trace=proxy_trace if telemetry else None)

    # -- run-state split / merge (continuous batching) ------------------------

    #: per-kind fields holding per-row (or CFG-doubled) device carries,
    #: with each field's batch axis — branch caches are scan-stacked
    #: ``(repeat, batch·{1,2}, ...)`` so their batch axis is 1; everything
    #: else in a run state is shared across rows
    _ROW_FIELDS = {
        RunState: (("x", 0), ("state", 0), ("cache", 1), ("label", 0),
                   ("memory", 0), ("healthy", 0)),
        AdaptiveRunState: (("x", 0), ("state", 0), ("cache", 1),
                           ("label", 0), ("memory", 0), ("healthy", 0),
                           ("x_prev", 0), ("acc", 0), ("lag", 0),
                           ("want", 0)),
        FusedAdaptiveRunState: (("x", 0), ("state", 0), ("cache", 1),
                                ("label", 0), ("memory", 0),
                                ("healthy", 0), ("x_prev", 0), ("acc", 0),
                                ("lag", 0)),
    }

    @property
    def supports_split(self) -> bool:
        """Whether run states are divisible values (:meth:`split_run` /
        :meth:`merge_runs`): requires a deterministic solver — a
        stochastic solver's loop-key noise depends on the batch shape, so
        its rows are not batch-invariant."""
        return not self.solver.stochastic

    def _check_split(self, rs):
        if not self.supports_split:
            raise ValueError(
                f"solver {self.solver.name!r} is stochastic: run states "
                "are not divisible (loop-key noise is batch-shape-"
                "dependent, so split rows would diverge from their batch)")
        fields = self._ROW_FIELDS.get(type(rs))
        if fields is None:
            raise ValueError(
                f"not a divisible run state: {type(rs).__name__}")
        return fields

    def split_run(self, rs, groups) -> List[Any]:
        """Split one in-flight run into independent sub-runs over disjoint
        row groups — pure carry slicing along the batch axis (gathers
        only, no model compute), bit-identical per row: XLA keeps
        independent rows bitwise stable across batch shapes, so each
        sub-run advances exactly as its rows would have in the original
        batch.  τ>0 adaptive sub-runs carry their per-sample ``(B, T)``
        acc/lag rows with them and realize their OWN mask AND from the
        split point on — the per-sample-mask property boundary regroup
        exploits.  Rows not covered by any group are dropped (how
        per-row retry discards a poisoned sample).  Landing only on
        existing bucket shapes is the caller's job — the serving engine
        splits to power-of-two sizes so ``xla_program_count`` never
        grows."""
        fields = self._check_split(rs)
        batch = int(rs.x.shape[0])
        groups = [tuple(int(i) for i in g) for g in groups]
        if not groups:
            raise ValueError("split_run needs at least one row group")
        seen = set()
        for g in groups:
            if not g:
                raise ValueError("split groups must be non-empty")
            for i in g:
                if not 0 <= i < batch:
                    raise ValueError(
                        f"row index {i} out of range for batch {batch}")
                if i in seen:
                    raise ValueError(f"row index {i} appears in two groups")
                seen.add(i)
        out = []
        for g in groups:
            upd = {f: _take_rows(getattr(rs, f), g, batch, axis=ax)
                   for f, ax in fields}
            if isinstance(rs, RunState):
                upd["structs"] = _rescale_structs(rs.structs, batch, len(g))
            elif isinstance(rs, FusedAdaptiveRunState):
                sel = jnp.asarray(np.asarray(g, np.int32))
                upd["trace"] = jnp.take(rs.trace, sel, axis=1)
                if rs.proxy_trace is not None:
                    upd["proxy_trace"] = jnp.take(rs.proxy_trace, sel,
                                                  axis=1)
            out.append(dataclasses.replace(rs, **upd))
        return out

    def merge_runs(self, runs) -> Any:
        """Merge position-aligned sub-runs into one batch — the concat
        dual of :meth:`split_run`, bit-identical per row.  Runs must be
        of the same kind at the same position with the same execution
        parameters (same plan + segment index, or same schedule/τ/k_max/
        pool); per-row carries concatenate, shared parameters come from
        the first run.  From the merge point on, τ>0 adaptive decisions
        realize the AND over the union's rows — each row's acc/lag rows
        merge untouched, so no accumulated-error history is lost."""
        runs = list(runs)
        if not runs:
            raise ValueError("merge_runs needs at least one run")
        r0 = runs[0]
        fields = self._check_split(r0)
        if len(runs) == 1:
            return r0
        if any(type(r) is not type(r0) for r in runs[1:]):
            raise ValueError("cannot merge runs of different kinds")
        batches = [int(r.x.shape[0]) for r in runs]
        if isinstance(r0, RunState):
            for r in runs[1:]:
                if r.plan is not r0.plan and r.plan != r0.plan:
                    raise ValueError(
                        "cannot merge runs with different plans")
                if r.run_index != r0.run_index:
                    raise ValueError(
                        "cannot merge runs at different segments")
        else:
            for r in runs[1:]:
                if (r.schedule.content_key() != r0.schedule.content_key()
                        or r.tau != r0.tau or r.k_max != r0.k_max):
                    raise ValueError(
                        "cannot merge adaptive runs with different "
                        "schedule/tau/k_max")
                if r.step != r0.step:
                    raise ValueError(
                        "cannot merge adaptive runs at different steps")
            if isinstance(r0, AdaptiveRunState):
                if any(r.pool_types != r0.pool_types for r in runs[1:]):
                    raise ValueError(
                        "cannot merge runs over different pools")
            elif any(r.table is not r0.table and r.table != r0.table
                     for r in runs[1:]):
                raise ValueError("cannot merge runs over different pools")
        upd = {f: _concat_rows([getattr(r, f) for r in runs], batches,
                               axis=ax)
               for f, ax in fields}
        if isinstance(r0, RunState):
            upd["structs"] = _rescale_structs(r0.structs, batches[0],
                                              sum(batches))
        elif isinstance(r0, AdaptiveRunState):
            # split siblings share one realized history; a join brings a
            # different one — drop to the honest "no per-step record"
            # value rather than claim one side's history for all rows
            if any(r.decisions != r0.decisions for r in runs[1:]):
                upd["decisions"] = ()
        else:
            # per-row desired traces concat exactly; `decisions` (the AND
            # over rows) becomes conservative for pre-merge steps
            upd["trace"] = jnp.concatenate([r.trace for r in runs], axis=1)
            if all(r.proxy_trace is not None for r in runs):
                upd["proxy_trace"] = jnp.concatenate(
                    [r.proxy_trace for r in runs], axis=1)
            elif any(r.proxy_trace is not None for r in runs):
                # mixed telemetry: no honest merged trace exists
                upd["proxy_trace"] = None
        return dataclasses.replace(r0, **upd)

    # -- run-state snapshot seams (durable serving) ---------------------------

    @property
    def supports_export(self) -> bool:
        """Whether run states can cross a process boundary via
        :meth:`export_run` / :meth:`import_run` — true for all three run
        kinds of this executor (the durable layer checks the attribute so
        test fakes opt in explicitly)."""
        return True

    def export_run(self, rs) -> Tuple[str, Dict, Dict]:
        """Run state → ``(kind, arrays, static)``, the snapshot seam of
        the durable serving layer.  ``arrays`` is a pytree of device
        arrays (serializable host-side by ``repro.checkpoint.io``);
        ``static`` is the small JSON-safe position/parameter stamp needed
        to rebuild the rest.  Derived Python objects — plan, schedule,
        pool index, switch table, cache structs — are deliberately NOT
        exported: :meth:`import_run` rebuilds them from the serving
        entry, and the caller's provenance stamp (entry name/version,
        schedule fingerprint, plan hash) is what guarantees it rebuilds
        the *same* ones.  Reading the arrays is a boundary transfer the
        host was already allowed to make — never a per-step sync, so a
        fused run's ``host_sync_count`` stays untouched."""
        if isinstance(rs, RunState):
            arrays = {"x": rs.x, "state": rs.state, "cache": rs.cache,
                      "kloop": rs.kloop, "label": rs.label,
                      "memory": rs.memory, "healthy": rs.healthy}
            static = {"batch": int(rs.x.shape[0]),
                      "run_index": int(rs.run_index)}
            return "plan", arrays, static
        if isinstance(rs, AdaptiveRunState):
            arrays = {"x": rs.x, "state": rs.state, "cache": rs.cache,
                      "kloop": rs.kloop, "label": rs.label,
                      "memory": rs.memory, "healthy": rs.healthy,
                      "x_prev": rs.x_prev, "acc": rs.acc, "lag": rs.lag,
                      "want": rs.want}
            static = {"batch": int(rs.x.shape[0]), "step": int(rs.step),
                      "tau": float(rs.tau), "k_max": int(rs.k_max),
                      "decisions": [list(d) for d in rs.decisions]}
            return "adaptive", arrays, static
        if isinstance(rs, FusedAdaptiveRunState):
            arrays = {"x": rs.x, "state": rs.state, "cache": rs.cache,
                      "kloop": rs.kloop, "label": rs.label,
                      "memory": rs.memory, "healthy": rs.healthy,
                      "x_prev": rs.x_prev, "acc": rs.acc, "lag": rs.lag,
                      "trace": rs.trace, "proxy_trace": rs.proxy_trace}
            static = {"batch": int(rs.x.shape[0]), "step": int(rs.step),
                      "tau": float(rs.tau), "k_max": int(rs.k_max)}
            return "adaptive_fused", arrays, static
        raise ValueError(
            f"not an exportable run state: {type(rs).__name__}")

    def import_run(self, params, kind: str, arrays: Dict, static: Dict, *,
                   plan=None, schedule=None, tau: float = 0.0,
                   proxy_map=None, pool=None, k_max: int = 3):
        """``(kind, arrays, static)`` → run state, the inverse of
        :meth:`export_run`.  The entry-side parameters (``plan`` /
        ``schedule`` / ``tau`` / ``proxy_map`` / ``pool`` / ``k_max``)
        come from the serving entry the run launched under; every derived
        structure is rebuilt exactly as the matching ``start_*`` would
        build it, so advancing the restored state is bit-identical to
        advancing the original.  Parameter disagreements between the
        snapshot stamp and the entry are refused (``ValueError``), not
        absorbed — the caller quarantines and replays from start."""
        label = arrays.get("label")
        memory = arrays.get("memory")
        healthy = arrays.get("healthy")
        if kind == "plan":
            if plan is None:
                raise ValueError(
                    "import_run kind='plan' needs the plan= the run was "
                    "launched with")
            run_index = int(static["run_index"])
            if not 0 <= run_index <= len(plan.runs):
                raise ValueError(
                    f"snapshot run_index {run_index} out of range for a "
                    f"{len(plan.runs)}-segment plan — wrong plan?")
            x = arrays["x"]
            return RunState(
                x=x, state=arrays["state"], cache=arrays["cache"],
                kloop=arrays["kloop"], plan=plan, run_index=run_index,
                label=label, memory=memory,
                structs=self._branch_structs(params, x, label, memory),
                healthy=healthy)
        if kind not in ("adaptive", "adaptive_fused"):
            raise ValueError(f"unknown run kind {kind!r}")
        # defense in depth: the stamp's decision parameters must equal the
        # entry's — a drifted τ/k_max would silently change every decision
        # from the restore point on
        if float(static.get("tau", tau)) != float(tau) \
                or int(static.get("k_max", k_max)) != int(k_max):
            raise ValueError(
                f"snapshot tau/k_max ({static.get('tau')}/"
                f"{static.get('k_max')}) disagree with the serving entry "
                f"({float(tau)}/{int(k_max)})")
        step = int(static["step"])
        if kind == "adaptive":
            schedule, tau, pool, by_skipset, pool_types, coeff_a, \
                coeff_b = self._adaptive_setup(schedule, tau, proxy_map,
                                               pool, k_max)
            if step > schedule.num_steps:
                raise ValueError(
                    f"snapshot step {step} exceeds the schedule's "
                    f"{schedule.num_steps} steps — wrong schedule?")
            return AdaptiveRunState(
                x=arrays["x"], state=arrays["state"],
                cache=arrays["cache"], kloop=arrays["kloop"], step=step,
                x_prev=arrays.get("x_prev"), acc=arrays["acc"],
                lag=arrays["lag"],
                decisions=tuple(tuple(d)
                                for d in static.get("decisions", ())),
                schedule=schedule, tau=tau, proxy_map=proxy_map,
                by_skipset=by_skipset, pool_types=pool_types,
                coeff_a=coeff_a, coeff_b=coeff_b, k_max=int(k_max),
                label=label, memory=memory, healthy=healthy,
                want=arrays.get("want"))
        schedule, tau, table, runtime, skip_table, coeff_a, coeff_b = \
            self._fused_setup(schedule, tau, proxy_map, pool, k_max)
        if step > schedule.num_steps:
            raise ValueError(
                f"snapshot step {step} exceeds the schedule's "
                f"{schedule.num_steps} steps — wrong schedule?")
        return FusedAdaptiveRunState(
            x=arrays["x"], x_prev=arrays["x_prev"], state=arrays["state"],
            cache=arrays["cache"], acc=arrays["acc"], lag=arrays["lag"],
            trace=arrays["trace"], kloop=arrays["kloop"], step=step,
            schedule=schedule, tau=tau, k_max=int(k_max), table=table,
            runtime=runtime, skip_table=skip_table, coeff_a=coeff_a,
            coeff_b=coeff_b, label=label, memory=memory, healthy=healthy,
            proxy_trace=arrays.get("proxy_trace"))

    # -- whole-sampler lowering (for FLOP / roofline accounting) ------------

    def build_sampler_fn(self, schedule):
        """A single jit-able function unrolling all steps of the (liveness-
        pruned) plan — ``jax.jit(fn).lower(...)`` exposes total FLOPs/bytes.
        Compile time scales with step count; use ``sample_compiled`` for
        actual sampling."""
        s_total = self.solver.num_steps
        plan = self.plan_for(schedule)

        def fn(params, x, label=None, memory=None, key=None):
            state = self.solver.init_state()
            cache = empty_branch_cache(self.cfg)
            for s in range(s_total):
                t = jnp.full((x.shape[0],), self.solver.model_times[s])
                # unrolled, so exact per-step liveness is free: collect only
                # what the next step reads, keep only what stays live
                pred, cache = self._sig_step(
                    params, x, t, label, memory, cache,
                    skip=plan.sig_at(s).skip, collect=plan.collect_at(s),
                    live=plan.live_out_at(s))
                kstep = (jax.random.fold_in(key, s)
                         if key is not None else None)
                x, state = self.solver.step(x, pred, s, state, kstep)
            return x

        return fn
