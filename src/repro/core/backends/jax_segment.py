"""Whole-segment jit compilation — the compiled backend.

A ``"jax"`` segment contains only ops whose selected implementation is a
*traceable* jax-tier function (``PhysicalImpl.traceable``).  Instead of
dispatching them one by one through python, this backend traces the whole
segment into ONE jitted program:

* **inputs** — values produced outside the compute set (earlier segments,
  intermediate-cache hits, preemption salvage) enter as runtime arguments;
* **tunable constants** — spec fields declared via
  :func:`repro.core.dag.declare_tunable` (``alpha``, ``l1_ratio``, ...)
  are hoisted to traced scalar arguments, so hyperparameter variants of
  the same structure reuse one compiled program with zero retraces;
* **outputs** — every computed op's outputs are returned and stored back
  into the runtime's value store, so cache inserts, liveness freeing and
  preemption salvage behave exactly as on the per-op path.

Compiled programs live in a :class:`~repro.core.plan_cache.PlanCache`
keyed by the segment's structural signature plus the runtime *cut* (which
ops were served from cache/salvage and therefore became inputs).  The
cache is shared per service shard, so a thousand structurally identical
agent plans compile once and then pay one dispatch per segment.

**Batched variant solves** (``batch_variants=True``): ops inside one
segment that share a structural signature and implementation but differ in
hoisted tunable values (an agent's hyperparameter sweep, coalesced into
one plan) are grouped and traced as ONE ``jax.vmap`` call over stacked
tunable columns — a single batched solve feeding the MXU instead of N
sequential solves unrolled in the program.  Inputs shared across members
(the common design matrix) pass through unbatched (``in_axes=None``);
inputs that differ are stacked.  Outputs are unstacked per member before
commit, so salvage, cache inserts and telemetry are byte-identical to the
unbatched path.  Grouping is a pure function of the plan-cache key, and
batched keys carry a distinct tag, so programs built with and without the
knob never mix.

**Async compilation**: when the plan cache owns a
:class:`~repro.core.plan_cache.CompileExecutor` (``compile_async=True``),
a cache miss no longer blocks the round on trace+jit.  The backend snaps
the segment's shape (proxy ops, wiring, input avals) into a closure,
enqueues it on the executor — single-flight, so concurrent tenants racing
on the same new signature compile once — and dispatches the current round
per-op through the fallback path (variant groups still vmap-batched
there).  The background job probes, builds, warm-calls on zero-filled
inputs and publishes to the cache; the next structurally identical round
runs compiled.  ``precompile_segment`` feeds the same machinery
speculatively: a predictor (e.g. the AIDE driver's next-refinement guess)
can enqueue likely-next shapes at low priority before any tenant submits
them, using observed input avals (falling back to inferred metadata) to
warm the exact program.

Semantics at the boundary: the intermediate cache is probed (one
tenant-aware ``get`` per op) *before* tracing — hits become inputs, not
traced ops — and marked candidates are inserted after execution;
cooperative preemption yields between segments.  Failure handling keeps
the "degrades performance, never correctness" contract: a segment shape
that fails a trace-only ``jax.eval_shape`` probe (mis-declared traceable
impl) is remembered as uncompilable — kept out of the plan cache so hit
rates stay honest, in an LRU bounded by ``uncompilable_max`` so an
adversarial stream of distinct bad shapes cannot grow a shard's memory —
and runs per-op forever after (a batched build that fails its probe first
retries unbatched before giving up); a *runtime* failure of a compiled
program (possibly transient, e.g. resource exhaustion, or a compile the
device refuses on the first call) falls back per-op for that round only,
reproducing any precise per-op error exactly as the uncompiled path
would.  Each such round counts in ``RunReport.segment_runtime_failures``
and the plan cache's ``runtime_failures`` (failed background warm calls
in ``warm_failures``), so a device that refuses the compiled path is
visible in telemetry.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import spans
from ..dag import LazyOp, tunable_fields
from ..plan_cache import PlanCache
from .base import ExecutionBackend

_EXT, _INT = 0, 1


class _TracedOp:
    """Stand-in for a LazyOp during tracing: exposes exactly the surface
    impl functions read (``op_name``/``op_class``/``spec``/``n_outputs``)
    without pinning the source plan's DAG — no ``inputs``, no ``meta``, so
    a cached compiled segment never keeps a whole submitted plan alive.

    Reading ``seed`` raises: seed *values* are excluded from structural
    signatures, so a traceable impl consuming one would bake this plan's
    seed into a program reused by seed-variants of the same structure.
    The trap turns that contract violation into a trace-time error — the
    backend falls back to per-op execution, degrading performance, never
    correctness."""

    __slots__ = ("op_name", "op_class", "spec", "n_outputs")

    def __init__(self, op_name: str, op_class: str, spec: dict,
                 n_outputs: int):
        self.op_name = op_name
        self.op_class = op_class
        self.spec = spec
        self.n_outputs = n_outputs

    @classmethod
    def of(cls, op: LazyOp) -> "_TracedOp":
        return cls(op.op_name, op.op_class, dict(op.spec), op.n_outputs)

    def with_spec(self, spec: dict) -> "_TracedOp":
        return _TracedOp(self.op_name, self.op_class, spec, self.n_outputs)

    @property
    def seed(self):
        raise TypeError(
            "op.seed is unavailable inside a compiled segment: seed values "
            "are not part of the structural signature, so a traceable impl "
            "must not read them (mark the impl traceable=False)")


class JaxSegmentBackend(ExecutionBackend):
    name = "jax"

    def __init__(self, plan_cache: Optional[PlanCache] = None,
                 batch_variants: bool = False,
                 uncompilable_max: int = 1024):
        # a private cache when none is injected: a bare Runtime still
        # benefits within its own lifetime; services inject the shared
        # per-shard cache so all tenants reuse each other's compiles
        self.plan_cache = plan_cache if plan_cache is not None \
            else PlanCache()
        self.batch_variants = bool(batch_variants)
        # programs built with variant batching are traced differently, so
        # they key under a distinct tag — the off path stays byte-identical
        self._key_tag = "jax-seg-vb" if self.batch_variants else "jax-seg"
        # segment shapes whose tracing failed (mis-declared traceable
        # impl): go straight to per-op, never re-trace.  Kept OUT of the
        # plan cache so its hit rate reflects compiled reuse only, and
        # bounded so one bad impl on an open-ended stream of distinct
        # structures cannot grow a shard's memory without limit.  Guarded
        # by its own lock: background compile jobs mark entries too.
        self._uncompilable: "OrderedDict" = OrderedDict()
        self._uncompilable_max = max(1, int(uncompilable_max))
        self._unc_lock = threading.Lock()
        # keys whose eval_shape probe the static analyzer already
        # discharged (analysis.preverify_segment): first dispatch builds
        # and jits without re-probing.  Advisory only — a key absent here
        # just probes as before.  Shares _unc_lock with _uncompilable.
        self._preverified: "OrderedDict" = OrderedDict()
        self._preverified_max = max(1, int(uncompilable_max))
        # observed avals of segment-external inputs, keyed by the input
        # ref's full signature: speculative precompiles warm with the
        # exact runtime (shape, dtype) instead of trusting inferred
        # metadata, so the warmed program matches the real dispatch
        self._ext_avals: "OrderedDict[str, tuple]" = OrderedDict()
        self._ext_avals_max = 4096
        self._aval_lock = threading.Lock()

    # ------------------------------------------------------------------
    def execute_segment(self, rt, segment, selection, report) -> None:
        report.waves += len(segment.waves)
        compute: list[LazyOp] = []
        produced: set[str] = set()
        for wave in segment.waves:
            for op in wave.ops:
                sig = op.signature
                if sig in rt._skips:
                    rt._mark_salvaged(op, report)
                    continue
                if sig in produced:
                    continue      # identical-signature peer: one compute
                if sig in rt.preloaded:
                    rt._store(op, rt.preloaded[sig])
                    rt._mark_salvaged(op, report)
                    continue
                # one tenant-aware probe; the hit becomes a segment
                # input instead of a traced op
                if rt._try_cache_hit(op, report) is not None:
                    continue
                compute.append(op)
                produced.add(sig)
        if compute:
            self._run_compiled(rt, segment, compute, selection, report)
        # liveness freeing at the segment boundary (the planner's
        # est_peak_mem accounts for the deferral — see scheduler.plan)
        for wave in segment.waves:
            rt._free_wave(wave)

    # ------------------------------------------------------------------
    def _wiring(self, compute: Sequence[LazyOp]):
        """Input wiring for the compute set: per op, each input is either
        (_INT, producer_position, out_index) — produced inside the segment
        — or (_EXT, arg_position, 0) — fetched from the value store."""
        pos_by_sig: dict[str, int] = {}
        for i, op in enumerate(compute):
            pos_by_sig.setdefault(op.signature, i)
        ext_keys: list[str] = []
        ext_index: dict[str, int] = {}
        in_specs = []
        for op in compute:
            specs = []
            for r in op.inputs:
                p = pos_by_sig.get(r.op.signature)
                if p is not None:
                    specs.append((_INT, p, r.index))
                else:
                    key = r.signature
                    j = ext_index.get(key)
                    if j is None:
                        j = ext_index[key] = len(ext_keys)
                        ext_keys.append(key)
                    specs.append((_EXT, j, 0))
            in_specs.append(tuple(specs))
        return tuple(in_specs), ext_keys

    def _fallback(self, rt, segment, compute, selection, report) -> None:
        """Per-op execution of the segment's compute set, wave-aligned so
        it keeps the python path's pool parallelism, vmap variant
        batching and intra-wave preemption polls — the fallback must
        never be worse than running with compiled segments disabled."""
        pending = {id(op) for op in compute}
        for wave in segment.waves:
            wave_ops = [op for op in wave.ops if id(op) in pending]
            if wave_ops:
                todo = rt._batch_variants(wave_ops, selection, report)
                rt._run_ops_parallel(todo, selection, report)

    # -- uncompilable bookkeeping --------------------------------------

    def _is_uncompilable(self, key) -> bool:
        with self._unc_lock:
            return key in self._uncompilable

    def _mark_uncompilable(self, key) -> None:
        with self._unc_lock:
            self._uncompilable[key] = True
            self._uncompilable.move_to_end(key)
            while len(self._uncompilable) > self._uncompilable_max:
                self._uncompilable.popitem(last=False)
            n = len(self._uncompilable)
        self.plan_cache.note_uncompilable(n)

    # -- statically pre-verified segments (analysis feasibility pass) ---

    def mark_preverified(self, key) -> None:
        with self._unc_lock:
            self._preverified[key] = True
            self._preverified.move_to_end(key)
            while len(self._preverified) > self._preverified_max:
                self._preverified.popitem(last=False)

    def _is_preverified(self, key) -> bool:
        with self._unc_lock:
            return key in self._preverified

    def _program_of(self, compute, selection):
        """What defines the program of a compute set, for every path that
        builds, compiles or looks one up: ``(key, name, in_specs,
        ext_keys, hoists, hoist_vals)``.  The key holds the structure of
        every traced op, the cut (which inputs are external) and the exact
        impl chosen (fidelity annotations can swap impls between
        structurally identical plans).  The name, ``seg_<digest>``, is the
        same program's in every process: a content hash of the structure,
        the cut and each impl's op, tier and fidelity, never an object's
        id, so device events, ``stratum.segment.run`` spans and JAX's
        persistent compilation cache all see one name."""
        in_specs, ext_keys = self._wiring(compute)
        hoists = tuple(tuple(sorted(tunable_fields(op.op_name)
                                    & set(op.spec))) for op in compute)
        ssigs = tuple(op.structural_signature for op in compute)
        impls = [selection[op.signature] for op in compute]
        key = (self._key_tag, ssigs, in_specs, tuple(map(id, impls)))
        digest = hashlib.blake2b(repr((
            self._key_tag, ssigs, in_specs,
            tuple((i.op_name, i.backend, i.fidelity) for i in impls),
        )).encode(), digest_size=8).hexdigest()
        hoist_vals = tuple(op.spec[f]
                           for op, fs in zip(compute, hoists)
                           for f in fs)
        return key, "seg_" + digest, in_specs, ext_keys, hoists, hoist_vals

    @staticmethod
    def _sources(compute, selection):
        """Trace stand-ins and impl functions of a compute set: all a
        build reads, so a cached program never pins the submitted DAG."""
        return ([_TracedOp.of(op) for op in compute],
                [selection[op.signature].fn for op in compute])

    def segment_program(self, segment, selection):
        """The unbatched program a first dispatch of ``segment`` builds
        when no op of it is served from a cache: ``(key, seg_fn, jitted,
        ext_refs, hoist_vals)``, where ``ext_refs`` holds one LazyRef per
        runtime argument, in argument order.  None when an op has no
        selected impl.  Builds only; never traces or compiles."""
        compute: list = []
        produced: set = set()
        for wave in segment.waves:
            for op in wave.ops:
                if op.signature in produced:
                    continue
                compute.append(op)
                produced.add(op.signature)
        if not compute or any(op.signature not in selection
                              for op in compute):
            return None
        key, name, in_specs, ext_keys, hoists, hoist_vals = \
            self._program_of(compute, selection)
        ref_by_key = {r.signature: r for op in compute for r in op.inputs
                      if r.op.signature not in produced}
        seg_fn, jitted = self._build(*self._sources(compute, selection),
                                     in_specs, hoists, (), name)
        return (key, seg_fn, jitted, [ref_by_key[k] for k in ext_keys],
                hoist_vals)

    def preverify_segment(self, segment, selection, infos):
        """Statically discharge a segment's first-dispatch probe.

        Builds the segment program exactly as ``_run_compiled`` would and
        ``eval_shape``-probes it on the analyzer's inferred input avals
        (``infos``: op signature -> list[TensorInfo]).  On success the
        plan-cache key is marked pre-verified and returned; on failure
        returns None and changes nothing — inferred avals may be less
        precise than runtime values, so a static miss must never poison
        the runtime's own probe.  Never executes or compiles."""
        try:
            program = self.segment_program(segment, selection)
            if program is None:
                return None
            key, seg_fn, _jitted, ext_refs, hoist_example = program
            ext_info = []
            for r in ext_refs:
                outs = infos.get(r.op.signature)
                if outs is None or r.index >= len(outs):
                    return None
                ext_info.append(outs[r.index])
            ext_example = tuple(
                jax.ShapeDtypeStruct(tuple(t.shape), np.dtype(t.dtype))
                for t in ext_info)
            jax.eval_shape(seg_fn, ext_example, hoist_example)
        except Exception:  # noqa: BLE001 — advisory probe, stay silent
            return None
        self.mark_preverified(key)
        return key

    # -- observed input avals (speculative warm-up fidelity) -----------

    @staticmethod
    def _aval_of(v):
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            return ("arr", tuple(v.shape), str(v.dtype))
        return ("raw", v)

    def _note_ext(self, ext_keys, ext_vals) -> None:
        with self._aval_lock:
            for k, v in zip(ext_keys, ext_vals):
                a = self._aval_of(v)
                if a[0] == "raw" and not isinstance(
                        v, (int, float, bool, str, bytes, type(None))):
                    continue   # don't pin arbitrary host objects
                self._ext_avals[k] = a
                self._ext_avals.move_to_end(k)
            while len(self._ext_avals) > self._ext_avals_max:
                self._ext_avals.popitem(last=False)

    @staticmethod
    def _zeros(ext_specs):
        """Zero-filled stand-ins matching recorded avals — numpy zeros
        share the jit aval of the runtime jax arrays (shape, dtype,
        weak_type=False), so warming on them compiles the exact program
        the real dispatch will look up."""
        out = []
        for spec in ext_specs:
            if spec[0] == "arr":
                _, shape, dtype = spec
                out.append(np.zeros(shape, dtype))
            else:
                out.append(spec[1])
        return tuple(out)

    # ------------------------------------------------------------------
    def _run_compiled(self, rt, segment, compute, selection,
                      report) -> None:
        key, name, in_specs, ext_keys, hoists, hoist_vals = \
            self._program_of(compute, selection)
        spans.annotate(program=name)
        if self._is_uncompilable(key):
            self._fallback(rt, segment, compute, selection, report)
            return
        with rt._lock:
            ext_vals = tuple(rt._values[k] for k in ext_keys)
        rt._note_crossing("jax", ext_vals, report)
        if self.plan_cache.executor is not None:
            self._note_ext(ext_keys, ext_vals)
        compiled = self.plan_cache.get(key)
        with rt._lock:
            if compiled is None:
                report.plan_cache_misses += 1
            else:
                report.plan_cache_hits += 1
        if compiled is None:
            groups = self._plan_groups(key[1], key[3], in_specs, hoists) \
                if self.batch_variants else ()
            protos, impl_fns = self._sources(compute, selection)
            ex = self.plan_cache.executor
            if ex is not None:
                # async: build off the critical path, run this round
                # per-op.  The job closes over proxies and avals only —
                # never the submitted DAG.
                specs = tuple(self._aval_of(v) for v in ext_vals)
                ex.submit(key, self._make_job(
                    key, name, protos, impl_fns, in_specs, hoists, groups,
                    specs, hoist_vals, speculative=False))
                with rt._lock:
                    report.plan_cache_fallback_rounds += 1
                self._fallback(rt, segment, compute, selection, report)
                return
            compiled = self._build_probed(
                key, name, protos, impl_fns, in_specs, hoists, groups,
                ext_vals, hoist_vals)
            if compiled is None:
                # per-op reproduces any precise error
                self._fallback(rt, segment, compute, selection, report)
                return
            self.plan_cache.put(key, compiled)
        try:
            with spans.span("stratum.segment.run", program=name):
                outs = compiled(ext_vals, hoist_vals)
        except Exception:  # noqa: BLE001 — XLA compile or runtime failure
            # possibly transient (e.g. resource exhaustion): run per-op
            # this round WITHOUT forgetting the compiled program — tracing
            # failures were already excluded by the eval_shape probe, so the
            # next structurally identical plan tries compiled again.  jit
            # compiles on this first call, so a program the device refuses
            # lands here too: counted, never silent
            with rt._lock:
                report.segment_runtime_failures += 1
            self.plan_cache.note_runtime_failure()
            self._fallback(rt, segment, compute, selection, report)
            return
        self._commit(rt, compute, outs, selection, report)

    def _make_job(self, key, name, protos, impl_fns, in_specs, hoists,
                  groups, ext_specs, hoist_vals, speculative: bool):
        """Background compile closure: probe → build → warm-call on
        zero-filled inputs → publish.  A runtime failure of the warm call
        on zeros (value-dependent, e.g. a singular solve) does not block
        publication — the probe already passed, matching the sync path's
        contract where such programs fall back per-op one round at a
        time."""
        def job():
            zeros = self._zeros(ext_specs)
            jitted = self._build_probed(
                key, name, protos, impl_fns, in_specs, hoists, groups,
                zeros, hoist_vals)
            if jitted is None:
                return           # marked uncompilable; demand runs per-op
            try:
                jax.block_until_ready(jitted(zeros, hoist_vals))
            except Exception:  # noqa: BLE001 — value-dependent on zeros
                self.plan_cache.note_warm_failure()
            self.plan_cache.put(key, jitted, speculative=speculative)
        return job

    def _build_probed(self, key, name, protos, impl_fns, in_specs, hoists,
                      groups, ext_example, hoist_example):
        """Build + abstract-trace probe, batched first.  A batched build
        whose probe fails (non-uniform member shapes, an impl vmap can't
        lift) silently retries unbatched; only when the plain build also
        fails to trace is the shape marked uncompilable.  eval_shape never
        lowers/compiles, so each probe costs a fraction of the real
        compile it precedes."""
        for gs in ((groups, ()) if groups else ((),)):
            seg_fn, jitted = self._build(protos, impl_fns, in_specs,
                                         hoists, gs, name)
            if not gs and self._is_preverified(key):
                # the static analyzer already eval_shape-probed this exact
                # build (analysis feasibility pass) — skip the re-probe.
                # Batched (gs) builds still probe: vmap-liftability is a
                # separate question the analyzer does not answer.
                return jitted
            try:
                jax.eval_shape(seg_fn, ext_example, hoist_example)
                return jitted
            except Exception:  # noqa: BLE001 — tracing failure
                continue
        self._mark_uncompilable(key)
        return None

    # -- variant-group planning ----------------------------------------

    @staticmethod
    def _plan_groups(ssigs, impl_ids, in_specs, hoists):
        """Homogeneous variant groups, as a pure function of the plan-cache
        key components (so every plan that maps to the key gets the same
        grouping).  Members share a structural signature and impl — same
        non-tunable spec, same wiring shape.  What varies per member is
        the batched axis: hoisted tunable values, differing inputs, or
        both — so a whole refinement chain (clip → impute → scale → fit →
        predict → metric) collapses stage by stage into batched calls,
        not just the tunable-carrying ops.  (Members with nothing varying
        cannot exist past CSE; a degenerate group fails the vmap probe
        and retries unbatched.)  A group executes at its LAST member's
        position; any group whose deferral would starve an earlier
        consumer (an internal edge whose producer moves past its reader)
        is dropped, checked to fixpoint since dropping one group shifts
        execution positions."""
        classes: dict = {}
        for i, (s, m) in enumerate(zip(ssigs, impl_ids)):
            classes.setdefault((s, m), []).append(i)
        groups = [tuple(g) for g in classes.values() if len(g) >= 2]
        while groups:
            group_of = {}
            last = {}
            for gi, g in enumerate(groups):
                for i in g:
                    group_of[i] = gi
                last[gi] = max(g)

            def exec_pos(i):
                return last[group_of[i]] if i in group_of else i

            bad = set()
            for i, specs in enumerate(in_specs):
                for tag, p, _oi in specs:
                    if tag == _INT and exec_pos(p) >= exec_pos(i):
                        bad.add(group_of[p] if p in group_of
                                else group_of[i])
            if not bad:
                break
            groups = [g for gi, g in enumerate(groups) if gi not in bad]
        return tuple(groups)

    # ------------------------------------------------------------------
    def _build(self, protos, impl_fns, in_specs, hoists, groups, name):
        """Returns ``(seg_fn, jitted)`` — the raw traceable function (for
        the abstract-trace probe) and its jit wrapper (what the plan
        cache stores), the function named ``name`` so its program is
        ``jit_<name>``.  Takes proxies + impl functions, never LazyOps:
        background compile jobs must not pin submitted DAGs.

        With ``groups``, each variant group becomes ONE ``jax.vmap`` call:
        per-member hoisted tunables stack into (k,) columns (``in_axes=0``
        each); per-member inputs that are the same traced value pass
        through shared (``in_axes=None``), differing ones stack on a new
        leading axis.  Outputs unstack per member, so everything
        downstream — later traced ops, commit, salvage — is oblivious."""
        n = len(protos)
        h_idx, h = [], 0
        for fs in hoists:
            h_idx.append(tuple(range(h, h + len(fs))))
            h += len(fs)
        group_of, last = {}, {}
        for gi, g in enumerate(groups):
            for i in g:
                group_of[i] = gi
            last[gi] = max(g)

        def gather(i, ext_vals, outs):
            return [ext_vals[j] if tag == _EXT else outs[j][oi]
                    for tag, j, oi in in_specs[i]]

        def run_one(i, ext_vals, hoist_vals, outs):
            op = protos[i]
            if hoists[i]:
                # fresh spec per trace: tracers must not leak into the
                # shared proto (concurrent retraces would race on it)
                spec = dict(op.spec)
                for f, hx in zip(hoists[i], h_idx[i]):
                    spec[f] = hoist_vals[hx]
                op = op.with_spec(spec)
            o = impl_fns[i](op, gather(i, ext_vals, outs))
            return o if isinstance(o, tuple) else (o,)

        def run_group(gi, ext_vals, hoist_vals, outs):
            members = groups[gi]
            proto, fn = protos[members[0]], impl_fns[members[0]]
            fields = hoists[members[0]]
            per_in = [gather(m, ext_vals, outs) for m in members]
            axes, bins = [], []
            for t in range(len(per_in[0])):
                vals = [row[t] for row in per_in]
                if all(v is vals[0] for v in vals[1:]):
                    axes.append(None)       # shared (the design matrix)
                    bins.append(vals[0])
                else:
                    axes.append(0)          # member-varying: stack
                    bins.append(jnp.stack(vals))
            h_cols = tuple(
                jnp.stack([jnp.asarray(hoist_vals[h_idx[m][t]])
                           for m in members])
                for t in range(len(fields)))

            def member_fn(hv, ins):
                spec = dict(proto.spec)
                for f, v in zip(fields, hv):
                    spec[f] = v
                o = fn(proto.with_spec(spec), list(ins))
                return o if isinstance(o, tuple) else (o,)

            stacked = jax.vmap(
                member_fn,
                in_axes=((0,) * len(fields), tuple(axes)))(
                h_cols, tuple(bins))
            for q, m in enumerate(members):
                outs[m] = tuple(o[q] for o in stacked)

        def seg_fn(ext_vals, hoist_vals):
            outs: list = [None] * n
            for i in range(n):
                gi = group_of.get(i)
                if gi is None:
                    outs[i] = run_one(i, ext_vals, hoist_vals, outs)
                elif i == last[gi]:
                    run_group(gi, ext_vals, hoist_vals, outs)
            return tuple(outs)

        seg_fn.__name__ = seg_fn.__qualname__ = name
        return seg_fn, jax.jit(seg_fn)

    # -- speculative warm-up -------------------------------------------

    def precompile_segment(self, segment, selection, cache=None) -> str:
        """Enqueue a low-priority background compile for a segment of a
        plan that has NOT been submitted — the speculative warm-up hook.
        Simulates the runtime cut against the intermediate cache
        side-effect-free (``in`` probes only: no hit counting, no LRU
        touch, no tenant attribution — the plan is hypothetical), derives
        the same plan-cache key the real dispatch would, and submits on
        the speculative lane.  Input avals come from observations of the
        same input signatures on real runs, falling back to inferred op
        metadata.  Returns a status string (for telemetry/tests):
        ``enqueued`` | ``cached`` | ``inflight`` | ``uncompilable`` |
        ``rejected`` (lane full / closed) | ``no-executor`` | ``empty`` |
        ``no-spec`` (an input's aval is unknown)."""
        ex = self.plan_cache.executor
        if ex is None:
            return "no-executor"
        compute: list[LazyOp] = []
        produced: set[str] = set()
        for wave in segment.waves:
            for op in wave.ops:
                sig = op.signature
                if sig in produced:
                    continue
                if cache is not None and sig in cache:
                    continue      # would be served as a segment input
                compute.append(op)
                produced.add(sig)
        if not compute:
            return "empty"
        key, name, in_specs, ext_keys, hoists, hoist_vals = \
            self._program_of(compute, selection)
        if self._is_uncompilable(key):
            return "uncompilable"
        if key in self.plan_cache:
            return "cached"
        if ex.inflight(key):
            return "inflight"
        ref_by_sig: dict = {}
        for op in compute:
            for r in op.inputs:
                ref_by_sig.setdefault(r.signature, r)
        specs = []
        with self._aval_lock:
            observed = {k: self._ext_avals.get(k) for k in ext_keys}
        for k in ext_keys:
            a = observed.get(k)
            if a is None:
                r = ref_by_sig[k]
                try:
                    ti = r.op.meta.outputs[r.index]
                    a = ("arr", tuple(ti.shape), ti.dtype)
                except Exception:  # noqa: BLE001 — no inferred metadata
                    return "no-spec"
            specs.append(a)
        groups = self._plan_groups(key[1], key[3], in_specs, hoists) \
            if self.batch_variants else ()
        protos, impl_fns = self._sources(compute, selection)
        ok = ex.submit(key, self._make_job(
            key, name, protos, impl_fns, in_specs, hoists, groups,
            tuple(specs), hoist_vals, speculative=True), speculative=True)
        return "enqueued" if ok else "rejected"

    # ------------------------------------------------------------------
    def _commit(self, rt, compute, outs, selection, report) -> None:
        from ..runtime import ExecutionError
        for op, out in zip(compute, outs):
            if len(out) != op.n_outputs:
                raise ExecutionError(
                    op, ValueError(f"impl returned {len(out)} outputs, "
                                   f"declared {op.n_outputs}"))
            rt._store(op, out)
            with rt._lock:
                report.ops_executed += 1
                report.per_backend["jax-seg"] = \
                    report.per_backend.get("jax-seg", 0) + 1
                report.sig_source[op.signature] = "jax-seg"
            rt._cache_put(op, out)
