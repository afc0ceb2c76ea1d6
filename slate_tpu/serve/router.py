"""serve.Router: admission -> accuracy class -> cached batched dispatch.

The thin request layer tying the serving pieces to the observability
stack PRs 7–10 built:

- **Admission** rides ``MemoryModel.predict_max_n``: a request whose
  modeled residency exceeds the per-request HBM budget is rejected
  before any pod time is burned (``serve.admission_rejects``).
- **Accuracy class** rides the cached condition estimate (the Carson &
  Higham three-precision regime boundary already encoded in
  ``numerics.CONDEST_THRESHOLD``): friendly general operators dispatch
  the cheap no-pivot f32 factor + iterative refinement; operators whose
  condest crosses the threshold dispatch partial pivoting + GMRES-IR
  (the stall regime where classic IR on a cheap factor diverges).  The
  estimate is memoized per operand buffer, so a stationary operator
  pays the Hager–Higham probe loop once across its request stream.
- **Dispatch** goes through the executable cache: same-shaped requests
  stack into one compiled batch program (serve/batch.py).  The stacked
  single-chip programs have no schedule knobs, so tuned options are
  NOT folded into their cache keys (a re-tuned table must not re-key
  programs it cannot affect); the autotuned table's consumers are the
  mesh request paths (batch.posv_packed_mesh resolves explicit >
  context > env > tuned > auto into nb/BcastImpl/Lookahead).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..types import Norm, Options, SlateError
from . import trace as rtrace
from .batch import DEFAULT_BINS, bin_for, pad_rhs_to_bin, pad_to_bin, \
    record_batch_size
from .cache import ExecutableCache, executable_cache, make_key
from .metrics import serve_count


class _BufferMemo:
    """Small LRU keyed on operand buffer identity (id()), holding a
    strong reference to the key array so the id cannot be recycled
    while the entry lives — the stationary-operator cache pattern
    (condest, digit planes).  Capped: serving traffic rotates through a
    handful of stationary operators, not thousands."""

    def __init__(self, cap: int = 16) -> None:
        self._cap = cap
        self._entries: OrderedDict = OrderedDict()

    def get(self, arr, extra=()) -> Optional[object]:
        key = (id(arr),) + tuple(extra)
        hit = self._entries.get(key)
        if hit is None:
            return None
        ref, value = hit
        if ref is not arr:  # id recycled across a dropped entry
            del self._entries[key]
            return None
        self._entries.move_to_end(key)
        return value

    def put(self, arr, value, extra=()) -> None:
        key = (id(arr),) + tuple(extra)
        self._entries[key] = (arr, value)
        self._entries.move_to_end(key)
        while len(self._entries) > self._cap:
            self._entries.popitem(last=False)

    def clear(self) -> None:
        self._entries.clear()


# Process-wide admission memo (ISSUE 19 satellite): the MemoryModel
# closed forms are pure in (model op, nb, grid, dtype, budget), so the
# hot dequeue path must not re-evaluate them per Router instance —
# every actual evaluation counts ``serve.max_n_computes`` (the queue
# smoke asserts a steady-state 100-request stream computes each key
# exactly once, however many Routers the service layer builds).
_MAX_N_MEMO: Dict[Tuple, int] = {}


class Router:
    """Synchronous request router over the batched drivers.

    ``solve_batch`` is the serving entry: a list of (op, a, b) requests
    is admitted, classified, binned into canonical shapes, stacked, and
    dispatched through the executable cache — steady-state traffic of a
    bounded shape vocabulary touches a handful of compiled programs and
    never re-traces."""

    def __init__(self, mesh=None, nb: int = 64,
                 bins: Sequence[int] = DEFAULT_BINS,
                 hbm_budget: Optional[int] = None,
                 cache: Optional[ExecutableCache] = None,
                 opts: Optional[Options] = None) -> None:
        from ..obs import memmodel

        self.mesh = mesh
        self.nb = nb
        self.bins = tuple(sorted(bins))
        self.cache = cache if cache is not None else executable_cache
        self.opts = dict(opts) if opts else {}
        self._budget = hbm_budget if hbm_budget is not None else int(
            memmodel.hbm_budget() * memmodel.HBM_SAFETY)
        self._max_n: Dict[str, int] = {}
        self._condest_memo = _BufferMemo()
        # precision-tier entry point per accuracy class (ISSUE 19): the
        # ServiceController's escalation knob.  Empty = identity; e.g.
        # {"friendly": "hostile"} makes friendly-classified operators
        # ENTER at the pp+GMRES-IR tier (the Carson–Higham robust
        # regime) instead of the cheap nopiv+IR tier.
        self.tier_map: Dict[str, str] = {}

    # -- admission ---------------------------------------------------------

    def max_n(self, op: str) -> int:
        """Largest admissible n for ``op`` under the HBM budget (modeled
        per-device peak, memmodel.predict_max_n; memoized process-wide
        per (model op, nb, grid, dtype, budget) with a per-instance L1
        — the hot dequeue path never re-evaluates a closed form)."""
        from ..obs import memmodel

        got = self._max_n.get(op)
        if got is None:
            # QR/eig requests carry their own models (ISSUE 15): the
            # multi-array aux outputs (T_loc/tree stacks, reflector/WY
            # stacks) made the old getrf_nopiv fallback over-admit them
            model_op = {"posv": "potrf", "potrf": "potrf",
                        "gemm": "summa", "summa": "summa",
                        "geqrf": "geqrf", "gels": "geqrf",
                        "heev": "he2hb", "he2hb": "he2hb"}.get(
                            op, "getrf_nopiv")
            grid = ((1, 1) if self.mesh is None
                    else tuple(self.mesh.devices.shape))
            key = (model_op, max(self.nb, 8), grid, "float64", self._budget)
            got = _MAX_N_MEMO.get(key)
            if got is None:
                serve_count("max_n_computes")
                got = memmodel.predict_max_n(
                    self._budget, op=model_op, nb=max(self.nb, 8),
                    grid=grid, dtype="float64")
                _MAX_N_MEMO[key] = got
            self._max_n[op] = got
        return got

    def admit(self, op: str, n: int) -> None:
        if n > self.max_n(op):
            serve_count("admission_rejects")
            raise SlateError(
                f"serve admission: {op} n={n} exceeds modeled HBM budget "
                f"(max admissible n={self.max_n(op)}, budget "
                f"{self._budget / 2**30:.2f} GiB)")

    def admit_batch(self, op: str, m: int, count: int, itemsize: int) -> None:
        """Aggregate residency check for one stacked dispatch: the whole
        (count, m, m) operand stack + RHS/solution + factor transients
        live at once in the single program (per-problem admission bounds
        one problem, not the stack).  ~3.5 stack copies covers operand +
        factor + solution + XLA temps for the mapped bodies."""
        agg = 3.5 * count * m * m * itemsize
        if agg > self._budget:
            serve_count("admission_rejects")
            raise SlateError(
                f"serve admission: batch of {count} x {op} n={m} needs "
                f"~{agg / 2**30:.2f} GiB aggregate, over the "
                f"{self._budget / 2**30:.2f} GiB budget — split the batch")

    # -- accuracy class ----------------------------------------------------

    def classify(self, op: str, a: jax.Array) -> str:
        """"friendly" | "hostile" per the cached reciprocal condition
        estimate.  The f32 probe factor is cheap (it is also the factor
        the friendly path would reuse conceptually); a stationary
        operator's estimate is memoized on its buffer identity, so a
        million-solve request stream pays the probe loop once."""
        from ..linalg import norms
        from ..obs.numerics import CONDEST_THRESHOLD

        if not jnp.issubdtype(a.dtype, jnp.floating) or a.dtype != jnp.float64:
            return "friendly"  # accuracy ladder is the f64 story
        cached = self._condest_memo.get(a, (op,))
        if cached is None:
            from ..linalg.lu import getrf_array

            anorm = jnp.abs(a).sum(axis=0).max()  # one-norm
            f = getrf_array(a.astype(jnp.float32))
            rcond = norms.gecondest(Norm.One, f, anorm)
            cached = float(rcond)
            self._condest_memo.put(a, cached, (op,))
        else:
            serve_count("condest_cache_hits")
        cond = (1.0 / cached) if cached > 0 else float("inf")
        hostile = cond > CONDEST_THRESHOLD
        serve_count("class_hostile" if hostile else "class_friendly")
        return "hostile" if hostile else "friendly"

    def effective_class(self, op: str, a: jax.Array) -> str:
        """The accuracy class ``solve_batch`` will dispatch ``(op, a)``
        under — condest classification (memoized, so the batch-window
        queue probing it at submit time and the dispatch re-deriving it
        pay the Hager–Higham loop once) composed with the controller's
        ``tier_map`` entry-point override.  The queue's window key uses
        this so one window always lands in one stacked program."""
        if op == "gesv" and not self._mesh_resilient(op):
            klass = self.classify(op, a)
        else:
            klass = "friendly"
        return self.tier_map.get(klass, klass)

    # -- dispatch ----------------------------------------------------------

    def _key_for(self, op: str, variant: str,
                 args: Tuple[jax.Array, ...], batch: int):
        # the ONE source of the stacked-program cache key (the request
        # tracer's hit/miss probe must agree with the lookup by
        # construction).  The stacked single-chip programs have NO
        # schedule knobs (no broadcasts, no k-loop pipelining), so tuned
        # options are deliberately NOT folded into their cache keys — a
        # re-tuned table must not re-key (and re-trace) programs it
        # cannot affect.  The tuned tier's consumers are the mesh paths
        # (batch.posv_packed_mesh resolves it into nb/BcastImpl/
        # Lookahead for the packed solve).
        return make_key(f"{op}_{variant}", args, batch=batch, mesh=None)

    def solve_batch(self, requests: Sequence[Tuple[str, jax.Array, jax.Array]],
                    tenants: Optional[Sequence[Optional[str]]] = None,
                    traces: Optional[List] = None) -> List[jax.Array]:
        """Serve a list of (op, a, b) requests (op in {"posv", "gesv"}).
        Returns per-request solutions in order.  Same-class requests
        sharing a bin run as ONE stacked compiled program (ragged sizes
        identity-pad to the bin; the padded rows solve an appended
        identity system and never touch data rows).

        ``tenants`` optionally names the submitting tenant per request
        (ISSUE 17): with the obs layer on, every metric, span, sample
        and gauge recorded under that request's phases carries the
        tenant tag (and the request's trace_id on event records); with
        obs off the argument is inert — no trace, no context, no tag.

        With the obs layer enabled, every request carries a
        ``RequestTrace`` (serve/trace.py) across its whole lifecycle —
        admission → classify → cache lookup → solve (plus the mesh
        path's factor/solve/degradation phases) — terminated with
        exactly one outcome; disabled, the tracer allocates nothing and
        the dispatch below is byte-identical.  A failure anywhere
        aborts the WHOLE call, so on the error path every still-open
        sibling trace terminates as ``reject_batch_abort`` (the request
        that actually failed already carries its own outcome) — the
        exactly-one-terminal contract holds for every request on every
        exit.

        ``traces`` optionally hands in pre-created RequestTraces (the
        batch-window queue opens a request's trace at SUBMIT time, so
        its latency covers the window wait); entries left ``None`` get
        a fresh trace per the obs-on/off contract, and the batch-abort
        sweep covers handed-in traces identically."""
        trs: List[Optional[rtrace.RequestTrace]] = (
            list(traces) if traces is not None else [None] * len(requests))
        try:
            with obs.driver_span("serve.solve_batch"):
                return self._solve_batch_inner(requests, trs, tenants)
        except Exception:
            for tr in trs:
                if tr is not None and tr.outcome is None:
                    tr.finish("reject_batch_abort")
            raise

    def _solve_batch_inner(self, requests, traces, tenants=None):
        # host phases as driver spans (serve.admit, serve.stack,
        # serve.lookup, serve.dispatch, serve.info, serve.unstack): a
        # profiler trace names the Router's host time with obs off
        with obs.driver_span("serve.admit"):
            groups, padded = self._admit_all(requests, traces, tenants)
        out: List[Optional[jax.Array]] = [None] * len(requests)
        for (op, klass, m, nrhs, _dt), idxs in groups.items():
            self._solve_group(requests, traces, padded, out, op, klass, m, idxs)
        return out  # type: ignore[return-value]

    def _admit_all(self, requests, traces, tenants):
        """Bin, admit, classify and pad every request; group them by
        (op, class, bin, nrhs, dtype).  Returns (groups, padded)."""
        groups: Dict[Tuple, List[int]] = {}
        padded: List[Optional[Tuple[jax.Array, jax.Array]]] = [None] * len(requests)
        for i, (op, a, b) in enumerate(requests):
            serve_count("requests")
            n = a.shape[0]
            tr = traces[i]
            if tr is None:
                tr = traces[i] = rtrace.new_trace(
                    op, n, self.nb, str(a.dtype),
                    tenant=tenants[i] if tenants else None)
            try:
                with rtrace.phase(tr, "admission"):
                    m = bin_for(n, self.bins)
                    if m is None:
                        serve_count("admission_rejects")
                        raise SlateError(
                            f"serve: n={n} exceeds the largest bin "
                            f"{self.bins[-1]}")
                    # the program runs at the PADDED bin size
                    self.admit(op, m)
            except SlateError:
                rtrace.finish(tr, "reject_admission")
                raise
            if tr is not None:
                tr.bin = m
            # the resilient mesh path has its own dispatch (pp for gesv)
            # and never consumes the accuracy class — skip the condest
            # probe instead of paying it for a discarded label
            if op == "gesv" and not self._mesh_resilient(op):
                with rtrace.phase(tr, "classify"):
                    klass = self.classify(op, a)
            else:
                klass = "friendly"
            # the controller's precision-tier entry-point override
            # (ISSUE 19): an escalated class dispatches the robust tier
            # even for operators the condest probe called friendly
            klass = self.tier_map.get(klass, klass)
            if tr is not None:
                tr.klass = klass
            bd = b if b.ndim == 2 else b[:, None]
            padded[i] = (pad_to_bin(a, m), pad_rhs_to_bin(bd, m))
            groups.setdefault(
                (op, klass, m, bd.shape[1], str(a.dtype)), []).append(i)
        return groups, padded

    def _solve_group(self, requests, traces, padded, out, op, klass, m, idxs):
        """Stack one group, run its program and write each request's
        solution into ``out``."""
        trs = [traces[i] for i in idxs]
        for tr in trs:
            if tr is not None:
                tr.batch = len(idxs)
        with obs.driver_span("serve.stack"):
            a_stack = jnp.stack([padded[i][0] for i in idxs])
            b_stack = jnp.stack([padded[i][1] for i in idxs])
            try:
                self.admit_batch(op, m, len(idxs), a_stack.dtype.itemsize)
            except SlateError:
                for tr in trs:
                    rtrace.finish(tr, "reject_admission")
                raise
        record_batch_size(op, len(idxs))
        if self._mesh_resilient(op):
            xs, info = self._solve_group_mesh(op, a_stack, b_stack, trs)
        else:
            live = any(tr is not None for tr in trs)
            with obs.driver_span("serve.lookup"):
                key = self._key_for(op, klass, (a_stack, b_stack), len(idxs))
                # the membership probe exists only for the tracer's
                # hit/miss label; untraced dispatch skips it
                hit = self.cache.contains(key) if live else False
                with rtrace.phase_all(trs, "cache_lookup",
                                      result="hit" if hit else "miss"):
                    prog = self.cache.get_or_build(
                        key, lambda op=op, klass=klass: _build_batched(
                            op, klass))
            with rtrace.phase_all(trs, "solve"):
                # with obs on, the dispatch span carries the ambient
                # trace_id/tenant — the join point the unified Perfetto
                # export correlates the request track against
                with obs.driver_span("serve.dispatch", op=op,
                                     klass=klass, batch=len(idxs)):
                    xs, info = prog(a_stack, b_stack)
                    if live:
                        # fence so the span (and the SLA latency) covers
                        # the execution, not just the dispatch — the
                        # untraced path keeps JAX's async semantics
                        jax.block_until_ready(xs)
        serve_count("batches")
        serve_count("batched_solves", len(idxs))
        with obs.driver_span("serve.info"):
            infos = np.asarray(info)
        bad = [idxs[j] for j, v in enumerate(infos) if v != 0]
        if bad:
            for j, i in enumerate(idxs):
                if infos[j] != 0:
                    rtrace.finish(traces[i], "failed_info")
            # never silently serve a failed factorization's output
            raise SlateError(
                f"serve: {op} batch reported nonzero info for request "
                f"indices {bad} — operand(s) not factorizable in the "
                f"{klass} class")
        with obs.driver_span("serve.unstack"):
            for j, i in enumerate(idxs):
                n = requests[i][1].shape[0]
                xi = xs[j, :n]
                out[i] = xi[:, 0] if requests[i][2].ndim == 1 else xi
                rtrace.finish(traces[i])  # note-attributed served terminal

    def solve(self, op: str, a: jax.Array, b: jax.Array,
              tenant: Optional[str] = None) -> jax.Array:
        """One request through the full policy (a batch of one)."""
        return self.solve_batch([(op, a, b)],
                                tenants=[tenant] if tenant else None)[0]

    # -- graceful degradation (ISSUE 12 satellite) -------------------------
    #
    # When the router is armed with a resilience policy
    # (Option.FaultTolerance and/or Option.Checkpoint in its opts) and a
    # mesh, requests dispatch through the protected mesh drivers instead
    # of the stacked single-chip programs, and the router absorbs their
    # failure modes instead of surfacing them raw:
    #
    # - a transient FtError retries ONCE under FtPolicy.Recompute
    #   (``serve.retries``) before surfacing — a one-shot SDC costs one
    #   recompute, not a failed request;
    # - a Preempted factorization resumes from its checkpoint on the
    #   router's mesh (``serve.resumes``);
    # - a preempted-and-UNRESUMABLE request (killed before the first
    #   snapshot, or re-killed on resume) is admission-REJECTED
    #   (``serve.admission_rejects``) with a structured error — never
    #   served NaNs.

    def _ckpt_every(self):
        from ..ft.ckpt import resolve_checkpoint
        from ..types import Option, get_option

        # get_option, not dict.get: Options accepts string keys too
        return resolve_checkpoint(
            get_option(self.opts, Option.Checkpoint, default=None))

    def _mesh_resilient(self, op: str) -> bool:
        if self.mesh is None or op not in ("posv", "gesv"):
            return False
        from ..ft.policy import FtPolicy, resolve_policy

        return (resolve_policy(self.opts) != FtPolicy.Off
                or self._ckpt_every() is not None)

    def _solve_group_mesh(self, op: str, a_stack, b_stack, trs=None):
        xs, infos = [], []
        for i in range(a_stack.shape[0]):
            tr = trs[i] if trs is not None else None
            x, info = self._solve_one_mesh(op, a_stack[i], b_stack[i], tr)
            xs.append(x)
            infos.append(jnp.asarray(info, jnp.int32))
        return jnp.stack(xs), jnp.stack(infos)

    def _solve_one_mesh(self, op: str, a, b, tr=None):
        try:
            return self._solve_one_mesh_inner(op, a, b, tr)
        except Exception:
            # an error escaping THIS request's own dispatch (e.g. a
            # second FtError after the one retry, or an abort raised
            # inside a retry) is this request's failure, not a sibling's
            # — terminate it with its own cause so solve_batch's
            # batch-abort sweep only ever labels true bystanders
            if tr is not None and tr.outcome is None:
                tr.finish("failed_error")
            raise

    def _solve_one_mesh_inner(self, op: str, a, b, tr=None):
        from ..ft import ckpt as _ckpt
        from ..ft.policy import FtError, FtPolicy, resolve_policy

        from ..obs.numerics import GrowthAbort

        pol = resolve_policy(self.opts)
        try:
            return self._guard(op, a, b, *self._factor_solve_mesh(
                op, a, b, pol, tr), tr=tr)
        except _ckpt.Preempted as e:
            if e.checkpoint is None:
                serve_count("admission_rejects")
                rtrace.finish(tr, "reject_unresumable")
                raise SlateError(
                    f"serve: {op} request preempted at step {e.killed_at} "
                    "before its first checkpoint — rejected (unresumable), "
                    "not served NaNs") from e
            serve_count("resumes")
            rtrace.note(tr, "resume")
            try:
                with rtrace.phase(tr, "resume", killed_at=e.killed_at,
                                  from_step=e.checkpoint.step):
                    resumed = self._resume_solve(op, b, e.checkpoint, tr)
                return self._guard(op, a, b, *resumed, tr=tr)
            except _ckpt.Preempted as e2:
                serve_count("admission_rejects")
                rtrace.finish(tr, "reject_unresumable")
                raise SlateError(
                    f"serve: {op} request re-preempted on resume at step "
                    f"{e2.killed_at} — rejected") from e2
            except GrowthAbort:
                # the RESUMED no-pivot factor kept policing the gauge
                # (Checkpoint.growth_abort) and aborted: same escalation
                # as the uninterrupted abort — one pivoted retry
                serve_count("retries")
                rtrace.note(tr, "growth_retry")
                with rtrace.phase(tr, "retry", cause="growth_abort"):
                    retried = self._factor_solve_pp(op, a, b, tr=tr)
                return self._guard(op, a, b, *retried, tr=tr)
        except FtError:
            # transient-SDC class: one retry under the recompute policy;
            # a second FtError (persistent corruption) surfaces raw
            serve_count("retries")
            rtrace.note(tr, "ft_retry")
            with rtrace.phase(tr, "retry", cause="ft_error"):
                retried = self._factor_solve_mesh(
                    op, a, b, FtPolicy.Recompute, tr)
            return self._guard(op, a, b, *retried, tr=tr)

    def _guard(self, op: str, a, b, x, info, tr=None):
        """The resilient mesh path bypasses the batched drivers'
        condest-keyed accuracy ladder (the ABFT LU is no-pivot), so no
        solution leaves unvalidated: one residual check rejects a
        silently-inaccurate solve instead of serving it."""
        if int(info) != 0:
            return x, info  # caller surfaces nonzero info itself
        n = a.shape[0]
        eps = float(jnp.finfo(a.dtype).eps)
        scale = float(jnp.max(jnp.abs(a))) * max(
            float(jnp.max(jnp.abs(x))), 1.0) * n
        resid = float(jnp.max(jnp.abs(a @ x - b)))
        if not np.isfinite(resid) or resid > 1e6 * n * eps * max(scale, 1.0):
            serve_count("admission_rejects")
            rtrace.finish(tr, "reject_residual")
            raise SlateError(
                f"serve: {op} resilient-path solution failed the residual "
                f"gate (|Ax-b| max {resid:.3g}) — rejected, not served")
        return x, info

    def _resil_opts(self):
        """Raw schedule/monitor options the resilient mesh path forwards
        (the drivers' _la/_bi/_nm idiom — armed options must thread
        end-to-end, not silently drop to defaults)."""
        from ..types import Option, get_option

        return (get_option(self.opts, Option.Lookahead),
                get_option(self.opts, Option.BcastImpl),
                get_option(self.opts, Option.NumMonitor))

    def _factor_solve_mesh(self, op: str, a, b, pol, tr=None):
        from ..ft.ckpt import getrf_pp_ckpt, potrf_ckpt
        from ..ft.policy import FtPolicy
        from ..parallel.dist import from_dense

        every = self._ckpt_every()
        la, bi, nm = self._resil_opts()
        if pol != FtPolicy.Off:
            if every is not None:
                raise SlateError(
                    "serve: Option.FaultTolerance and Option.Checkpoint "
                    "cannot be combined (the ABFT kernels are not "
                    "checkpointed yet); arm one of them")
            from ..ft import abft

            with rtrace.phase(tr, "factor", method="abft", policy=str(pol)):
                if op == "posv":
                    l, info, _rep = abft.potrf_ft(
                        a, self.mesh, self.nb, policy=pol, lookahead=la,
                        bcast_impl=bi)
                else:
                    # the only ABFT LU is no-pivot — _guard validates the
                    # solution it produces
                    l, info, _rep = abft.getrf_nopiv_ft(
                        a, self.mesh, self.nb, policy=pol, lookahead=la,
                        bcast_impl=bi)
            return self._trsm_solve(op, l, b, tr=tr), info
        d = from_dense(a, self.mesh, self.nb, diag_pad_one=True)
        if op == "posv":
            with rtrace.phase(tr, "factor", method="potrf_ckpt"):
                l, info = potrf_ckpt(d, every=every, bcast_impl=bi,
                                     num_monitor=nm)
            return self._trsm_solve(op, l, b, tr=tr), info
        # gesv on the checkpointed path: with NumMonitor armed, try the
        # cheap no-pivot factor first — the FRIENDLY accuracy class the
        # batched router already serves (PR 11's condest-keyed nopiv+IR
        # dispatch), here policed by the segment chain's in-carry growth
        # gauge instead of a condest probe: element growth crossing
        # GROWTH_THRESHOLD ABORTS the factor mid-k-loop
        # (obs.numerics.GrowthAbort, ISSUE 13 satellite: never complete
        # a garbage factor) and the router consumes that as exactly one
        # retry with partial pivoting (``serve.retries``).  Served
        # growth below the threshold bounds the nopiv backward error at
        # ~GROWTH_THRESHOLD·eps64 ≈ 2e-10 — the friendly-class bar —
        # and _guard's residual gate backstops every served solution.
        # The class mix is observable: gauge-policed nopiv serves count
        # ``serve.class_friendly``, pp serves ``serve.class_hostile``.
        # Unmonitored requests keep partial pivoting outright — no
        # class downgrade without the gauge that polices it.
        from ..obs.numerics import GrowthAbort, resolve_num_monitor

        if resolve_num_monitor(nm) == "on":
            from ..ft.ckpt import getrf_nopiv_ckpt

            try:
                with rtrace.phase(tr, "factor", method="nopiv_ckpt"):
                    lu, info = getrf_nopiv_ckpt(
                        d, every=every, bcast_impl=bi, num_monitor=nm)
                serve_count("class_friendly")
                return self._trsm_solve(op, lu, b, tr=tr), info
            except GrowthAbort:
                serve_count("retries")
                rtrace.note(tr, "growth_retry")
                with rtrace.phase(tr, "retry", cause="growth_abort"):
                    return self._factor_solve_pp(op, b_dense=b, d=d, tr=tr)
        return self._factor_solve_pp(op, b_dense=b, d=d, tr=tr)

    def _factor_solve_pp(self, op: str, a=None, b_dense=None, d=None,
                         tr=None):
        """The pivoted gesv tier (shared by the growth-abort retry paths:
        the initial attempt hands over its DistMatrix, the resumed-abort
        path re-encodes from the dense operand)."""
        from ..ft.ckpt import getrf_pp_ckpt
        from ..parallel.dist import from_dense

        _la, bi, nm = self._resil_opts()
        if d is None:
            d = from_dense(a, self.mesh, self.nb, diag_pad_one=True)
        with rtrace.phase(tr, "factor", method="pp_ckpt"):
            lu, perm, info = getrf_pp_ckpt(d, every=self._ckpt_every(),
                                           bcast_impl=bi, num_monitor=nm)
        serve_count("class_hostile")
        return self._trsm_solve(op, lu, b_dense, perm=perm, tr=tr), info

    def _resume_solve(self, op: str, b, checkpoint, tr=None):
        from ..ft import elastic

        _la, bi, _nm = self._resil_opts()
        with rtrace.phase(tr, "factor", method="elastic_resume"):
            out = elastic.resume(checkpoint, self.mesh, bcast_impl=bi)
        if len(out) == 3:  # getrf_pp: (LU, perm, info)
            lu, perm, info = out
            return self._trsm_solve(op, lu, b, perm=perm, tr=tr), info
        l, info = out
        return self._trsm_solve(op, l, b, tr=tr), info

    def _trsm_solve(self, op: str, l, b, perm=None, tr=None):
        from ..parallel.dist import from_dense, to_dense
        from ..parallel.dist_lu import permute_rows_dist
        from ..parallel.dist_trsm import trsm_dist
        from ..types import Diag, Op, Uplo

        la, bi, _nm = self._resil_opts()
        with rtrace.phase(tr, "solve"):
            bd = from_dense(b, self.mesh, self.nb)
            if perm is not None:
                bd = permute_rows_dist(bd, perm)
            if op == "posv":
                y = trsm_dist(l, bd, Uplo.Lower, Op.NoTrans, lookahead=la,
                              bcast_impl=bi)
                x = trsm_dist(l, y, Uplo.Lower, Op.ConjTrans, lookahead=la,
                              bcast_impl=bi)
            else:
                y = trsm_dist(l, bd, Uplo.Lower, Op.NoTrans, Diag.Unit,
                              lookahead=la, bcast_impl=bi)
                x = trsm_dist(l, y, Uplo.Upper, Op.NoTrans, lookahead=la,
                              bcast_impl=bi)
            out = to_dense(x)[: b.shape[0]]
            if tr is not None:
                jax.block_until_ready(out)  # honest span/SLA end time
        return out

    # -- QR (least-squares) tier -------------------------------------------

    def gels(self, a: jax.Array, b: jax.Array,
             tenant: Optional[str] = None) -> jax.Array:
        """Serve one least-squares request min ||A x - b|| through the
        mesh CAQR tier (requires a mesh; m >= n).  With
        Option.NumMonitor armed the factor's recorded reflector/τ
        consistency loss (the ``num.qr_orth_margin`` gauge — recorded
        since ISSUE 15, acted on here) is policed against
        ``obs.numerics.ORTH_THRESHOLD``: a factor past the bound is NOT
        served raw — the router retries ONCE with a
        re-orthogonalization pass ("twice is enough": a second CAQR
        over the explicitly-formed Q, both triangular factors folded
        into the solve), counted as one ``serve.retries`` with its own
        degradation note (``orth_retry``).  Unmonitored requests keep
        the single-pass factor — no degradation action without the
        gauge that polices it (the growth-abort rule)."""
        from ..obs import numerics as _num
        from ..parallel.dist import from_dense, to_dense
        from ..parallel.dist_qr import geqrf_dist, unmqr_dist
        from ..types import Op

        if self.mesh is None:
            raise SlateError("serve: the gels tier requires a mesh")
        serve_count("requests")
        m, n = a.shape
        tr = rtrace.new_trace("gels", m, self.nb, str(a.dtype),
                              tenant=tenant)
        try:
            with rtrace.phase(tr, "admission"):
                self.admit("gels", m)
        except SlateError:
            rtrace.finish(tr, "reject_admission")
            raise
        try:
            _la, bi, nm = self._resil_opts()
            monitored = _num.resolve_num_monitor(nm) == "on"
            if monitored:
                _num.clear_last("geqrf")  # police THIS factor's gauge
            bcol = b if b.ndim == 2 else b[:, None]
            with rtrace.phase(tr, "factor", method="geqrf_dist"):
                f1 = geqrf_dist(from_dense(a, self.mesh, self.nb),
                                bcast_impl=bi, num_monitor=nm)
            if monitored and _num.orth_exceeded("geqrf"):
                serve_count("retries")
                rtrace.note(tr, "orth_retry")
                with rtrace.phase(tr, "retry", cause="orth_loss"):
                    # Q1 = Q2 R2 re-orthogonalizes the computed basis, so
                    # A = Q2 (R2 R1): solve R2 z = Q2ᴴ b, then R1 x = z
                    eye = jnp.eye(m, n, dtype=a.dtype)
                    q1 = to_dense(unmqr_dist(
                        f1, from_dense(eye, self.mesh, self.nb),
                        Op.NoTrans, bcast_impl=bi))[:, :n]
                    f2 = geqrf_dist(from_dense(q1, self.mesh, self.nb),
                                    bcast_impl=bi, num_monitor=nm)
                    qb = to_dense(unmqr_dist(
                        f2, from_dense(bcol, self.mesh, self.nb),
                        Op.ConjTrans, bcast_impl=bi))[:n]
                    z, info2 = self._rsolve(f2, qb, n, bi)
                    x, info1 = self._rsolve(f1, z, n, bi)
                    info = jnp.where(info1 != 0, info1, info2)
            else:
                with rtrace.phase(tr, "solve"):
                    qb = to_dense(unmqr_dist(
                        f1, from_dense(bcol, self.mesh, self.nb),
                        Op.ConjTrans, bcast_impl=bi))[:n]
                    x, info = self._rsolve(f1, qb, n, bi)
            if int(info) != 0:
                rtrace.finish(tr, "failed_info")
                raise SlateError(
                    f"serve: gels factor reported info={int(info)} — "
                    "R diagonal exactly zero (rank-deficient operand)")
            jax.block_until_ready(x)  # honest span/SLA end time
            rtrace.finish(tr)
            return x[:, 0] if b.ndim == 1 else x
        except Exception:
            if tr is not None and tr.outcome is None:
                tr.finish("failed_error")
            raise

    def _rsolve(self, f, y, n: int, bi):
        """x = R^{-1} y from CAQR factors: the R top square goes through
        one dense triu round trip (the gels_mesh composition) into an
        upper trsm sweep.  info flags an exactly-zero R diagonal."""
        from ..parallel.dist import from_dense, to_dense
        from ..parallel.dist_trsm import trsm_dist
        from ..types import Op, Uplo

        r = jnp.triu(to_dense(f.fact)[:n, :n])
        rd = from_dense(r, self.mesh, self.nb, diag_pad_one=True)
        xd = trsm_dist(rd, from_dense(y, self.mesh, self.nb), Uplo.Upper,
                       Op.NoTrans, bcast_impl=bi)
        rdiag = jnp.diagonal(r)
        info = jnp.where(
            jnp.any(rdiag == 0), jnp.argmax(rdiag == 0) + 1, 0
        ).astype(jnp.int32)
        return to_dense(xd)[:n], info


def _build_batched(op: str, variant: str):
    """The pure stacked solve body for one (op, accuracy-class) pair —
    what the executable cache jits and pins."""
    from jax import lax

    if op == "posv":
        from ..linalg.chol import posv_array

        def posv(a, b):
            def one(ab):
                x, _f, info = posv_array(ab[0], ab[1])
                return x, info

            return lax.map(one, (a, b))

        return posv
    if op != "gesv":
        raise ValueError(f"router has no batched driver for {op!r}")
    if variant == "hostile":
        # pp + GMRES-IR: the escalation class for operators past the
        # Carson–Higham IR stall boundary
        from ..linalg.refine import gesv_mixed_gmres_array

        def hostile(a, b):
            def one(ab):
                x, _resid = gesv_mixed_gmres_array(ab[0], ab[1])
                # GMRES-IR has no LAPACK info; a non-finite solution is
                # the observable factor/convergence failure signal
                ok = jnp.all(jnp.isfinite(x))
                return x, jnp.where(ok, 0, 1).astype(jnp.int32)

            return lax.map(one, (a, b))

        return hostile
    from ..linalg.lu import gesv_array, getrf_nopiv_array, getrs_array
    from ..linalg.refine import _fallback, _refine_loop

    def friendly(a, b):
        # cheap class: f32 no-pivot factor + f64 IR, full-solve fallback
        # (the pivot-free factor is the fast tier no-pivoting safety
        # analysis forbids for hostile operators — which is exactly why
        # the condest class gate sits in front of it)
        def one(ab):
            a1, b1 = ab
            f32 = getrf_nopiv_array(a1.astype(jnp.float32))
            solve = lambda r: getrs_array(f32, r.astype(jnp.float32))
            x, iters, done = _refine_loop(a1, b1, solve, 30)
            x, _iters, info = _fallback(
                done, x, iters,
                lambda: (lambda o: (o[0], o[1].info))(gesv_array(a1, b1)))
            return x, info

        return lax.map(one, (a, b))

    return friendly
