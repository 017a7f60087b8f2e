"""Resilient-solve orchestration: run → (inject failures) → recover → converge.

Generalizes the paper's experimental protocol (§4-§5) from one node-failure
event per run to a *failure scenario*: a list of ``FailureEvent(iter, nodes)``
entries, each injected at a marked iteration (the driver lands exactly on
it). An event may strike several nodes simultaneously (the multi-node case of
Pachajoa et al., arXiv:1907.13077), and events may be staggered — failure →
recover → fail again, including a second event landing before the next
completed storage stage, which rolls back to the *same* reconstruction point
again (or restarts when none exists). Rollback rewinds the iteration counter
below already-consumed events without re-arming them; validation (strictly
increasing event iterations) keeps every pending event ahead of the rewound
counter, so each fires exactly once. Failed nodes zero out all their dynamic
data and then act as their own replacements. Reported quantities match the
paper's tables — total runtime, reconstruction overhead, wasted iterations,
converged iteration count, residual drift (Eq. 2) — plus a per-event
breakdown (``SolveReport.events``).

The hot loop runs through a ``SolverOps`` bundle (repro.core.ops): Block-ELL
SpMV fused with the pᵀq dot, fused vector update, cond-gated storage
bookkeeping. Convergence uses a sync-free chunked protocol: each chunk
carries ||r|| as a done flag and freezes the state at first convergence, so
the driver never re-runs a chunk to land on the convergence iteration, and
the norm-record readback of chunk i overlaps with the dispatch of chunk i+1
instead of blocking between chunks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import elastic as elastic_mod
from repro.core import esr, esrp, imcr, sdc
from repro.core.aspmv import RedundancyPlan, build_plan, shrink_plan
from repro.core.failures import (FailureEvent, SDCEvent, failed_row_mask,
                                 normalize_scenario, zero_failed)
from repro.core.ops import SolverOps, batch_ops, make_closure_ops
from repro.core.pcg import PCGState, _vec_norm, freeze_pcg, residual_drift
from repro.core.tiers import resolve_tier
from repro.obs.trace import Tracer, jsonable, span
from repro.sparse.matrices import Problem

# version stamp of the report JSON layout (EventReport/SolveReport.to_json);
# bump on any field rename/removal so downstream BENCH consumers can branch.
# v2: SolveReport gained batch_index/batch_size (batched solves emit one
# report per member).
# v3: SolveReport gained deadline_missed/retries (annotated by the serving
# front-end) and final_n_nodes became required for report validation.
# v4: SolveReport gained prologue_s and runtime_s runs until the answer is on
# the device; EventReport gained event_s and inner_iters.
REPORT_SCHEMA_VERSION = 4


@dataclasses.dataclass
class EventReport:
    """Per-event recovery accounting (one entry per fired FailureEvent)."""
    iter: int                    # iteration J the event struck
    nodes: tuple[int, ...]
    target_iter: int             # reconstruction point (-1 = restart)
    wasted_iters: int            # rollback distance of this event
    recovery_s: float            # reconstruction ops only
    inner_rel: float             # Alg.2 line-8 inner solve (nan: imcr/none)
    pff_iters: int = -1          # Alg.2 line-6 inner-CG iterations (-1 when
    #                              the preconditioner has a closed form)
    inner_iters: int = 0         # Alg.2 line-8 inner PCG iterations (0:
    #                              imcr/none, or no reconstruction)
    event_s: float = 0.0         # fail-stop/sdc-repair: from the event
    #                              span's start to the end of the resume step
    #                              after it (its ||r|| read-back waits on the
    #                              device); the event span alone when no
    #                              resume step follows
    precond_reload_bytes: int = 0   # static preconditioner state the
    #                              replacement reloads from safe storage
    #                              (sharded runtime; see
    #                              precond.local.static_reload_bytes)
    queue_src_nodes: tuple[int, ...] = ()   # devices whose *physical* queue
    #                              shards supplied the failed rows'
    #                              p-copies (sharded runtime; empty on the
    #                              host-side simulator)
    kind: str = "fail-stop"      # "fail-stop" | "sdc-inject" | "sdc-repair"
    detector: str = ""           # sdc-repair: which invariant fired
    detect_iter: int = -1        # sdc-repair: iteration the check fired at
    detect_latency: int = -1     # detect_iter − injection iteration (≤ one
    #                              invariant-check period by construction)
    sdc_target: str = ""         # sdc-inject: corrupted array ("p"/"r"/...)
    sdc_violation: float = float("nan")   # the relative violation measured
    sdc_tol: float = float("nan")         # the tolerance it was compared to
    tier: str = ""               # storage tier the recovery read from
    fetch_bytes: int = 0         # redundancy bytes the recovery fetched
    fetch_s_model: float = 0.0   # tier cost model applied to fetch_bytes
    elastic_n_nodes: int = 0     # >0: node count the run continued on after
    #                              this event (elastic shrunk-mesh recovery)

    def to_json(self) -> dict:
        """JSON-safe dict (NaN/inf -> None, device scalars -> Python) with a
        ``schema_version`` stamp — the serialization the BENCH writers and
        the JSONL event log embed."""
        out = {f.name: jsonable(getattr(self, f.name))
               for f in dataclasses.fields(self)}
        out["schema_version"] = REPORT_SCHEMA_VERSION
        return out


@dataclasses.dataclass
class SolveReport:
    strategy: str
    T: int
    phi: int
    converged_iter: int
    rel_residual: float
    runtime_s: float             # the solve span: entry until the answer is
    #                              on the device and the reports are built
    recovery_s: float            # reconstruction ops only, summed over events
    wasted_iters: int            # rollback distance, summed over events
    target_iter: int             # last event's reconstruction point; -1 when
    #                              no reconstruction happened (restart, or no
    #                              failure event at all — see ``events``)
    inner_rel: float             # last event's Alg.2 line-8 inner-solve residual
    drift: float                 # paper Eq. (2)
    aspmv_natural_bytes: int = 0
    aspmv_total_bytes: int = 0
    run_calls: int = 0           # chunk dispatches (no final-chunk re-run)
    prologue_s: float = 0.0      # the prologue span: entry to the first
    #                              chunk dispatch (plan, norms, state init)
    events: list[EventReport] = dataclasses.field(default_factory=list)
    precond_variant: str = ""    # e.g. "node-local ssor" on the sharded
    #                              runtime (SolverOps.variant)
    spmv_format: str = ""        # the SpMV's storage (SolverOps.spmv_format):
    #                              "dia" | "blockell"; "" for closure ops
    spmv_diagonals: int = 0      # diagonals it stores ("dia" only)
    local_delta_iters: Optional[int] = None   # iteration-count delta of a
    #                              node-local run vs the global-sweep
    #                              reference (shard.attach_local_delta)
    converged: bool = True       # False: the run stopped at max_iters with
    #                              ||r|| still above threshold
    precond_reload_bytes: int = 0   # summed over events (sharded runtime)
    tier: str = ""               # redundancy storage tier (core.tiers)
    push_count: int = 0          # storage pushes executed over the run
    #                              (incl. re-pushes on rolled-back stretches)
    push_bytes: int = 0          # total bytes those pushes moved into the
    #                              tier (per-push volume × push_count)
    push_s_model: float = 0.0    # tier cost model over all pushes
    fetch_s_model: float = 0.0   # summed over events' recovery fetches
    sdc_checks: int = 0          # invariant checks evaluated
    sdc_check_every: int = 0     # the cadence they ran at (0 = SDC off)
    final_n_nodes: int = 0       # node count at convergence (shrinks under
    #                              elastic recovery)
    batch_index: int = 0         # this member's row in the batched solve
    batch_size: int = 1          # members the dispatch advanced together
    #                              (1 = plain unbatched solve)
    deadline_missed: bool = False   # serving front-end: the request's
    #                              per-request deadline expired before this
    #                              solve completed (the result may still be
    #                              numerically valid — see solver_service)
    retries: int = 0             # serving front-end: dispatch attempts the
    #                              micro-batch burned on unsurvivable events
    #                              before this solve succeeded
    x: Optional[object] = dataclasses.field(default=None, repr=False)
    #                              final iterate (device array) — lets parity
    #                              tests assert bit-identical rejoin; rel/
    #                              drift above are host-side norms whose flat
    #                              reduction may differ from the mesh's by
    #                              1 ulp even on identical vectors
    trace: Optional[object] = dataclasses.field(default=None, repr=False)
    #                              the obs.Tracer of this solve (obs=on only):
    #                              spans, counters, per-iteration history —
    #                              export via repro.obs.export

    def to_json(self) -> dict:
        """JSON-safe dict with a ``schema_version`` stamp. The device-array
        ``x`` and the live ``trace`` handle are dropped (neither serializes
        usefully; the tracer has its own exporters); NaN/inf coerce to None
        so ``json.dumps(..., allow_nan=False)`` always succeeds."""
        skip = {"x", "trace", "events"}
        out = {f.name: jsonable(getattr(self, f.name))
               for f in dataclasses.fields(self) if f.name not in skip}
        out["events"] = [e.to_json() for e in self.events]
        out["schema_version"] = REPORT_SCHEMA_VERSION
        return out


def _find_convergence(norms: np.ndarray, thresh: float) -> int:
    """Index of first iteration with ||r|| < thresh, or -1."""
    below = np.nonzero(norms < thresh)[0]
    return int(below[0]) if below.size else -1


def _in_solve_span(fn):
    """Run the solve inside its ``solve`` span. ``fn`` receives ``obs``
    resolved: the Tracer to record into (``True`` makes a fresh one), or
    None. Each report's ``runtime_s`` is the span's duration, so it ends
    after the answer is on the device and the reports are built."""
    @functools.wraps(fn)
    def wrapped(*args, obs=None, **kwargs):
        tr = obs if isinstance(obs, Tracer) else (
            Tracer("solve_resilient") if obs else None)
        with span("solve", "solver", tr) as sp:
            out = fn(*args, obs=tr, **kwargs)
        reports = out if isinstance(out, list) else [out]
        for rep in reports:
            rep.runtime_s = sp.dur_s
            if tr is not None:
                tr.record("solve_report", rep.to_json())
        return out
    return wrapped


# module-level so the trace cache survives across solves (a fresh jit wrapper
# per resume would recompile the same iteration every failure run).
# esrp.numeric_step (not bare pcg_iterate_ops): the resume iteration must run
# the same rr_every residual-replacement gate as the chunk runner, or a
# replacement landing on the reconstruction point would be silently skipped
# and the post-recovery trajectory would fork off the failure-free one.
_resume_step = jax.jit(esrp.numeric_step, static_argnums=(3, 4))


def _full_precision_matmuls(fn):
    """Trace ``fn``'s programs with f32 matmuls at full precision. On a TPU
    an f32 dot defaults to one bf16 pass, which would leave every inner
    product, the standalone preconditioner and Alg. 2's inner solves at
    about three digits; elsewhere the setting changes nothing."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


@_full_precision_matmuls
@_in_solve_span
def solve_resilient(
    problem: Problem,
    strategy: str = "esrp",            # "esrp" | "imcr" | "none"
    T: int = 20,
    phi: int = 1,
    rtol: float = 1e-8,
    max_iters: int = 100_000,
    fail_at: Optional[int] = None,     # legacy one-event shorthand
    failed_nodes: Optional[list[int]] = None,
    scenario: Optional[list[FailureEvent]] = None,   # multi-event scenario
    matvec: Optional[Callable] = None,
    chunk: int = 64,
    rr_every: int = 0,                 # residual replacement period (0 = off)
    backend: str = "auto",             # SolverOps backend for the hot loop
    ops: Optional[SolverOps] = None,   # explicit bundle (overrides backend)
    gated: bool = True,                # cond-gated storage/rr bookkeeping
    pff_precond: bool = True,          # precondition the Alg.2 line-6 inner
    #                                    CG (False = historical plain CG)
    failure_runtime=None,              # comm.shard.ShardedFailureRuntime:
    #                                    device-resident redundancy queue,
    #                                    shard_map injection, and recovery
    #                                    reads from surviving devices' shards
    sdc_policy: Optional[sdc.SDCPolicy] = None,   # enable the invariant
    #                                    checks (auto-enabled with defaults
    #                                    when the scenario holds an SDCEvent)
    sdc_on_device: bool = True,        # fold the invariant recomputation
    #                                    into the chunk tail on-device
    #                                    (esrp.run_chunk's halt guard):
    #                                    chunks no longer clamp to check
    #                                    boundaries and detection latency
    #                                    stays bounded by check_every even
    #                                    for long chunks. False restores the
    #                                    host-side between-chunk checks
    #                                    (every boundary forces a readback)
    storage_tier="device-neighbour",   # core.tiers name or StorageTier: the
    #                                    redundancy-queue placement cost model
    elastic: bool = False,             # no replacement nodes: after each
    #                                    fail-stop event, re-partition onto
    #                                    the shrunk node count and continue
    batch_fused: bool = False,         # batched throughput mode: fused-
    #                                    batched einsum ops (one op per
    #                                    iteration for all B members) in
    #                                    place of the per-member-unrolled
    #                                    exact bundle. Per-member results
    #                                    deviate from the B=1 run at ~ulp
    #                                    (convergence unaffected); the
    #                                    serving path opts in for >B x
    #                                    dispatch amortization
    rhs=None,                          # right-hand side override. A (B, M)
    #                                    array arms the BATCHED solve: all B
    #                                    systems (same A/P, different b)
    #                                    advance per dispatch with
    #                                    per-member convergence freeze, one
    #                                    FailureEvent strikes every member,
    #                                    one Alg. 2 pass rebuilds them all,
    #                                    and the return is a list of B
    #                                    per-member SolveReports. A (M,)
    #                                    array just replaces problem.b.
    obs=None,                          # observability: an obs.Tracer to
    #                                    record into, or True for a fresh
    #                                    one (returned as report.trace).
    #                                    Default off: the obs=off hot path
    #                                    is bit-identical and compiles to
    #                                    the identical jaxpr (tested)
) -> "SolveReport | list[SolveReport]":
    tr: Optional[Tracer] = obs        # resolved by _in_solve_span, which
    solve_sp = tr.current if tr is not None else None    # opened this span
    with span("prologue", "solver", tr) as pro_sp:
        part = problem.part
        pending = normalize_scenario(scenario, fail_at, failed_nodes,
                                     part.n_nodes)
        rhs_arr = None if rhs is None else jnp.asarray(rhs, problem.b.dtype)
        batched = rhs_arr is not None and rhs_arr.ndim == 2
        nbatch = int(rhs_arr.shape[0]) if batched else 0
        if rhs_arr is not None and rhs_arr.shape[-1] != part.m:
            raise ValueError(
                f"rhs row length {rhs_arr.shape[-1]} != problem size {part.m}")
        if failure_runtime is not None \
                and getattr(failure_runtime, "batch", 0) != nbatch:
            rt_batch = getattr(failure_runtime, "batch", 0)
            if nbatch:
                hint = (f"construct ShardedFailureRuntime(problem, mesh, "
                        f"batch={nbatch}) to match the (B, M) rhs")
            else:
                hint = ("this solve is unbatched — construct "
                        "ShardedFailureRuntime(problem, mesh) and leave the "
                        "batch parameter at its default 0")
            raise ValueError(
                f"failure_runtime was built for batch={rt_batch} but this "
                f"solve has batch={nbatch} — {hint}")
        sdc_events = [e for e in pending if isinstance(e, SDCEvent)]
        if sdc_events or sdc_policy is not None:
            if strategy not in ("esrp", "none"):
                raise ValueError(
                    f"SDC detection/repair supports the esrp and none strategies "
                    f"(got {strategy!r}): imcr's checkpoint protocol has no "
                    f"per-iteration invariants to verify against")
            if strategy == "esrp" and T == 1:
                raise ValueError(
                    "SDC with T=1 (ESR) is unsupported: ESR stores every "
                    "iteration, so corrupted state would be committed to the "
                    "redundancy queue before any check cadence could catch it — "
                    "use T >= 2")
            if strategy == "none" and any(e.target == "queue"
                                          for e in sdc_events):
                raise ValueError(
                    'strategy="none" keeps no redundancy queue — there is no '
                    '"queue" shard to corrupt')
            if sdc_policy is None:
                sdc_policy = sdc.SDCPolicy()
        sdc_on = sdc_policy is not None
        # on-device guard mode: the chunk runner verifies the invariants at
        # every check boundary inside the scan and halts on a violation; the
        # host only confirms + localizes at the halted state (sdc.run_checks)
        sdc_guard = sdc_on and sdc_on_device
        # per-push queue checksums: written at push time, compared at check and
        # read time (only meaningful when something both stores and checks)
        qsum_slabs = part.n_nodes if (sdc_on and strategy == "esrp") else 0
        if elastic:
            if strategy != "esrp":
                raise ValueError(
                    f"elastic shrunk-mesh recovery needs the esrp strategy (got "
                    f"{strategy!r}): Alg. 2 reconstruction provides the complete "
                    f"state the shrunk partition continues from")
            if failure_runtime is not None or ops is not None \
                    or matvec is not None:
                raise ValueError(
                    "elastic recovery re-partitions the problem and rebuilds its "
                    "solver ops — it requires the default problem-built ops (no "
                    "custom ops/matvec) and no sharded failure runtime")
        tier = resolve_tier(storage_tier)
        itemsize = np.dtype(problem.b.dtype).itemsize
        if ops is None:
            if matvec is not None:
                # cache the closure bundle on the problem so repeated solves with
                # the same matvec reuse the jitted chunk runners (a closure
                # bundle keys their compile cache by identity), without pinning
                # the problem in a module-global cache
                cache = getattr(problem, "_closure_ops_cache", None)
                if cache is None:
                    cache = {}
                    problem._closure_ops_cache = cache
                key = (matvec, problem.apply_precond, nbatch)
                if key not in cache:
                    base = make_closure_ops(matvec, problem.apply_precond)
                    cache[key] = batch_ops(base, nbatch) if batched else base
                ops = cache[key]
            else:
                ops = problem.solver_ops(backend, batch=nbatch,
                                         fused=batched and batch_fused)
        matvec = ops.matvec
        precond = ops.precond
        b = rhs_arr if rhs_arr is not None else problem.b
        bnorm = float(jnp.linalg.norm(b))
        if batched:
            # per-member threshold. Zero-RHS members (micro-batch padding) get
            # +inf: their rnorm == 0 row freezes at iteration 0 instead of
            # dividing by zero, and their report carries rel = 0 / converged
            bnorm_v = _vec_norm(b)
            thresh_dev = jnp.where(bnorm_v > 0, rtol * bnorm_v,
                                   jnp.inf).astype(b.dtype)
            thresh = np.asarray(thresh_dev)        # (B,) host copy, same values
            conv_iter = np.full(nbatch, -1, np.int64)   # per-member first
            #                                  crossing (set-once, absolute count)
        else:
            thresh_dev = jnp.asarray(rtol * bnorm, b.dtype)
            # host-side scans must compare against the *same* value the chunk
            # runner's freeze uses, or (in f32) a norm between the two would
            # freeze the device state without the host ever declaring convergence
            thresh = float(thresh_dev)

        mtr = tr is not None              # static: arms the chunk metrics ring

        plan: Optional[RedundancyPlan] = None
        push = None
        if strategy == "esrp":
            plan = build_plan(problem.a, part, phi)   # static, verified φ+1 copies
            if failure_runtime is not None:
                # device-resident redundancy: the storage pushes physically
                # place each node's p-tiles on the designated holder devices
                failure_runtime.bind_plan(plan)
                push = failure_runtime.queue_push
        dot = getattr(ops, "dot", None)

        if strategy == "imcr":
            st = imcr.imcr_init(matvec, precond, b, dot=dot)
            run = lambda s, n: imcr.run_chunk(s, ops, T, phi,
                                              part.rows_per_node, n,
                                              thresh_dev, gated, mtr)
        elif strategy == "esrp":
            st = esrp.esrp_init(matvec, precond, b, dot=dot, n_slabs=qsum_slabs)
            if failure_runtime is not None:
                st = failure_runtime.init_queue(st)
            run_chk = lambda s, n, chk: esrp.run_chunk(
                s, ops, T, n, thresh_dev, rr_every, gated, b, push, mtr, chk)
            run = lambda s, n: run_chk(s, n, sdc_policy if sdc_guard else None)
        elif strategy == "none":
            st = esrp.esrp_init(matvec, precond, b, dot=dot)  # T=max: no stores
            run_chk = lambda s, n, chk: esrp.run_chunk(
                s, ops, 1 << 30, n, thresh_dev, rr_every, gated, b, None, mtr,
                chk)
            run = lambda s, n: run_chk(s, n, sdc_policy if sdc_guard else None)
        else:
            raise ValueError(strategy)

        event_reports: list[EventReport] = []
        recovery_s = 0.0
        wasted = 0
        target = -1       # "no reconstruction point": restart or no event at all
        inner_rel = float("nan")
        # rr gating applies to the esrp/none runners only; imcr's chunk runner
        # has no replacement gate, so its resume must not add one either
        resume_rr = rr_every if strategy != "imcr" else 0

        # per-push tier volume: needed live (the settle-time byte counters), not
        # just in the end-of-run accounting; rebound on elastic re-partition
        per_push = (tier.push_bytes(plan, part.m, itemsize)
                    if strategy == "esrp" and plan is not None else 0)
        if tr is not None:
            nat0, tot0 = plan.bytes_per_aspmv(itemsize) if plan is not None \
                else (0, 0)
            solve_sp.args.update(
                strategy=strategy, T=T, phi=phi,
                backend=backend, variant=getattr(ops, "variant", ""),
                spmv_format=ops.spmv_format,
                spmv_diagonals=ops.spmv_diagonals, tier=tier.name, m=part.m,
                n_nodes=part.n_nodes, rtol=rtol,
                aspmv_natural_bytes=nat0, aspmv_total_bytes=tot0,
                per_push_bytes=per_push)

        total_iters = 0
        run_calls = 0
        resume_numeric_only = False
        converged = False
        sdc_checks = 0
        sdc_repairs = 0
        # injections whose corruption no repair has cleared yet, for the
        # detection-latency attribution: (injection iter, target)
        sdc_wait: list[tuple[int, str]] = []
        # iteration stretches actually executed (rollback re-executes, so pushes
        # re-happen) — the tier push accounting replays the storage schedule
        # over them after the run
        push_ranges: list[tuple[int, int]] = []
        # one chunk's norm record kept in flight: (device record, start
        # iteration, dispatched length, guard armed?). Readback (the host sync)
        # happens only after the *next* chunk has been dispatched, so device
        # compute and host bookkeeping overlap.
        inflight: Optional[tuple] = None
        # iteration count the on-device SDC guard halted at (-1 = no halt
        # pending); set by settle(), consumed by the main loop's check handler
        halt_iter = -1
        # armed when the device guard halted but the host check found nothing
        # (threshold-edge disagreement): the next dispatch steps one iteration
        # guard-free so the run cannot spin on the same boundary
        guard_skip = False
        # the last event whose resume step is still to come: (its report, its
        # span), so that the report's event_s can run on to that step's end
        resumed: Optional[tuple] = None

    def settle(entry) -> bool:
        """Block on one chunk's norm record; True iff it converged. The
        chunk runner froze the state at first convergence, so on a hit the
        live ``st`` already is the state at iteration base + hit + 1 — no
        re-run needed, only the count is fixed up.

        With obs on the record also carries the chunk's metrics-ring rows
        (same readback, zero extra dispatches): rows past the executed
        count repeated the frozen carry and are trimmed before they land in
        the tracer's iteration history.

        With the on-device SDC guard armed the record also carries the
        per-iteration halted flags: halted[i] = True means iteration
        base + i did NOT execute — the chunk froze at check boundary
        base + i with a violated invariant, and the live ``st`` is exactly
        the state entering it. The first halt index lands in ``halt_iter``
        (set-once: a chunk dispatched from an already-halted state re-halts
        at its own iteration 0 and must not overwrite the real boundary)."""
        nonlocal total_iters, converged, halt_iter
        record, base, n_disp, guarded = entry
        halt_d = None
        if guarded:
            (norms_d, aux_d, halt_d) = record if mtr else \
                (record[0], None, record[1])
        else:
            norms_d, aux_d = record if mtr else (record, None)
        with span("chunk_settle", "solver", tr, base=base, n=n_disp):
            norms = np.asarray(norms_d)
            h_rel = -1
            if halt_d is not None:
                hidx = np.nonzero(np.asarray(halt_d))[0]
                h_rel = int(hidx[0]) if hidx.size else -1
            if batched:
                # norms is (n_disp, B): the chunk is done when EVERY member
                # is below its own threshold; individual crossings are
                # recorded set-once (the device froze that member, so its
                # later rows just repeat the frozen norm)
                below = norms < thresh[None, :]
                allb = np.nonzero(below.all(axis=1))[0]
                hit = int(allb[0]) if allb.size else -1
                for k in range(nbatch):
                    if conv_iter[k] < 0:
                        idx = np.nonzero(below[:, k])[0]
                        if idx.size:
                            conv_iter[k] = base + int(idx[0]) + 1
            else:
                hit = _find_convergence(norms, thresh)
            if h_rel >= 0:
                # the guard skips once every member converged, so a halt
                # precludes an earlier full-convergence hit; rows from the
                # halt on are passthrough
                hit = -1
                if halt_iter < 0:
                    halt_iter = base + h_rel
            # iterations past a convergence hit ran frozen — no pushes
            executed = (h_rel if h_rel >= 0
                        else hit + 1 if hit >= 0 else n_disp)
            push_ranges.append((base, base + executed))
            if hit >= 0:
                total_iters = base + hit + 1
                converged = True
            if tr is not None and executed > 0:
                aux = np.asarray(aux_d)[:executed]
                if batched:
                    # per-member rows collapse to the tracks the exporters
                    # render: the max-norm (the convergence gate), the
                    # shared storage flags (identical across members), and
                    # the worst-member rz / orthogonality residual
                    tr.record_iters(np.arange(base, base + executed),
                                    rnorm=norms[:executed].max(axis=1),
                                    rz=aux[:, 0].max(axis=1),
                                    push=aux[:, 1, 0], star=aux[:, 2, 0],
                                    orth=aux[:, 3].max(axis=1))
                    n_push = int(round(float(aux[:, 1, 0].sum())))
                else:
                    tr.record_iters(np.arange(base, base + executed),
                                    rnorm=norms[:executed], rz=aux[:, 0],
                                    push=aux[:, 1], star=aux[:, 2],
                                    orth=aux[:, 3])
                    n_push = int(round(float(aux[:, 1].sum())))
                if n_push and per_push:
                    tr.add_counter("tier_push_bytes", n_push * per_push,
                                   pushes=n_push, tier=tier.name)
        return converged

    while not converged:
        if resume_numeric_only:
            # post-recovery: re-run the reconstruction-point iteration without
            # its storage prelude (its push already happened pre-failure) but
            # WITH the rr_every replacement gate (see _resume_step). Jitted so
            # the jnp backend fuses exactly like inside run_chunk — keeps the
            # cross-backend trajectory bit-identity through recovery.
            with span("resume_step", "solver", tr, iter=total_iters) as rs_sp:
                pcg_old = st.pcg
                pcg = _resume_step(pcg_old, ops, b, resume_rr, gated)
                if batched:
                    # members that were already converged (shielded from the
                    # event by the post-recovery member select) must not be
                    # stepped past their frozen state
                    done = _vec_norm(pcg_old.r) < thresh_dev
                    pcg = freeze_pcg(pcg_old, pcg, done)
                st = st._replace(pcg=pcg)
                total_iters = int(pcg.j)
                resume_numeric_only = False
                if batched:
                    rnorm_v = np.asarray(_vec_norm(pcg.r))
                    for k in range(nbatch):
                        if conv_iter[k] < 0 and rnorm_v[k] < thresh[k]:
                            conv_iter[k] = total_iters
                    rnorm = float(rnorm_v.max())
                    if tr is not None:
                        tr.record_iters([total_iters - 1], rnorm=[rnorm])
                    converged = bool((rnorm_v < thresh).all())
                else:
                    rnorm = float(jnp.linalg.norm(pcg.r))
                    if tr is not None:
                        # the re-run iteration's metrics row (the chunk ring
                        # never sees it); its push/star already happened on
                        # the pre-failure pass — dedup keeps this later row
                        tr.record_iters(
                            [total_iters - 1], rnorm=[rnorm],
                            rz=[float(pcg.rz)], push=[0.0], star=[0.0],
                            orth=[float(jnp.abs(pcg.r @ pcg.p - pcg.rz))])
                    converged = rnorm < thresh
            if resumed is not None:
                ev_rep, ev_sp = resumed
                ev_rep.event_s = (rs_sp.t1_us - ev_sp.t0_us) / 1e6
                resumed = None
            if converged:
                break
            continue

        n = chunk
        if pending:
            n = min(n, pending[0].iter - total_iters)
        if sdc_on and not sdc_guard:
            # host-side checks: land exactly on every invariant-check
            # boundary — the cadence, plus (ESRP) every storage iteration —
            # state must be verified clean BEFORE it is committed to the
            # queue/stars, or a later rollback would faithfully restore
            # corrupted copies. (The on-device guard verifies the same
            # boundaries inside the scan — before each boundary iteration's
            # prelude — so guard mode dispatches full chunks.)
            n = min(n, _next_sdc_boundary(
                total_iters, sdc_policy.check_every, T,
                strategy == "esrp") - total_iters)
        entry = None
        if guard_skip and n > 0:
            # device/host disagreement escape hatch: the guard halted but
            # the authoritative host check found nothing — step exactly one
            # iteration guard-free to move past the boundary
            with span("chunk_dispatch", "solver", tr, base=total_iters, n=1,
                      guard_skip=True):
                st, record = run_chk(st, 1, None)
            run_calls += 1
            entry = (record, total_iters, 1, False)
            total_iters += 1
            guard_skip = False
        elif n > 0:
            with span("chunk_dispatch", "solver", tr, base=total_iters, n=n):
                st, record = run(st, n)          # async dispatch
            run_calls += 1
            entry = (record, total_iters, n, sdc_guard)
            total_iters += n

        if inflight is not None:
            prev, inflight = inflight, None
            if settle(prev):
                break                            # entry (if any) discarded:
                #                                  the state is frozen past
                #                                  convergence by construction
        if halt_iter >= 0 and entry is not None:
            # the previous chunk halted at a check boundary, so this chunk
            # was dispatched from the frozen halted state: its guard
            # re-fired on entry and zero iterations executed — settle and
            # discard it (set-once halt_iter keeps the real boundary)
            settle(entry)
            entry = None
        at_fail = (halt_iter < 0 and bool(pending)
                   and total_iters == pending[0].iter)
        at_check = (halt_iter < 0 and sdc_on and not sdc_guard and not at_fail
                    and total_iters > 0
                    and _at_sdc_boundary(total_iters, sdc_policy.check_every,
                                         T, strategy == "esrp"))
        if entry is not None:
            if at_fail or at_check or total_iters >= max_iters:
                if settle(entry):
                    break
            else:
                inflight = entry                 # overlap with next dispatch
        from_halt = halt_iter >= 0
        if from_halt:
            # roll the count back to the halted boundary (== st.pcg.j); the
            # authoritative host check below localizes and repairs there.
            # A pending fail event is never at the halt (chunks clamp to
            # event iterations, and the halt lands strictly inside a chunk)
            total_iters = halt_iter
            halt_iter = -1
            at_fail = False
            at_check = True
        if total_iters >= max_iters:
            break

        if at_fail:
            ev = pending.pop(0)
            if any(nd >= part.n_nodes for nd in ev.nodes):
                raise ValueError(
                    f"event at iter {ev.iter} names node(s) {ev.nodes} "
                    f"outside the current {part.n_nodes}-node partition "
                    f"(elastic recovery shrank the mesh)")
            if isinstance(ev, SDCEvent):
                # silent corruption: iteration ev.iter executes with the
                # corruption struck mid-iteration; nothing stops, nothing is
                # reported to the solver — only an invariant check can catch
                # it downstream
                with span("event:sdc-inject", "event", tr,
                          iter=ev.iter, nodes=list(ev.nodes),
                          target=ev.target):
                    # already-converged members are shielded: their B=1
                    # reference runs ended before the corruption struck, so
                    # neither the injected flip nor the injection
                    # iteration's step may disturb their frozen state
                    st_pre = st if batched else None
                    done_pre = (_vec_norm(st.pcg.r) < thresh_dev) \
                        if batched else None
                    st = _inject_sdc(problem, st, ev,
                                     T if strategy == "esrp" else (1 << 30),
                                     ops, b, resume_rr, gated, push)
                    if batched:
                        st = esrp.member_select(st_pre, st, done_pre)
                total_iters = int(st.pcg.j)
                push_ranges.append((ev.iter, ev.iter + 1))
                sdc_wait.append((ev.iter, ev.target))
                event_reports.append(EventReport(
                    iter=ev.iter, nodes=ev.nodes, target_iter=total_iters,
                    wasted_iters=0, recovery_s=0.0, inner_rel=float("nan"),
                    kind="sdc-inject", sdc_target=ev.target, tier=tier.name))
                # the landing count may itself be a check boundary (e.g. the
                # event struck a first-push iteration, so the very next
                # iteration star-captures and pushes again): run the check
                # NOW, before any dispatch commits the corrupted state to
                # storage — otherwise a later rollback would faithfully
                # restore the corruption
                at_check = _at_sdc_boundary(total_iters,
                                            sdc_policy.check_every, T,
                                            strategy == "esrp")
            else:
                failed = list(ev.nodes)
                ev_inner = float("nan")
                ev_pff = -1
                ev_inner_iters = 0
                ev_reload = 0
                ev_src: tuple[int, ...] = ()
                ev_fetch = 0
                ev_fetch_s = 0.0
                # already-converged members are shielded from the event:
                # their B=1 reference run would have ended before it fired,
                # so injection + rollback must not disturb their frozen
                # state — the per-member select below restores it
                st_pre = st if batched else None
                done_pre = (_vec_norm(st.pcg.r) < thresh_dev) if batched \
                    else None
                with span("event:fail-stop", "event", tr,
                          iter=ev.iter, nodes=list(ev.nodes),
                          strategy=strategy) as ev_sp:
                    if strategy == "imcr":
                        st, ev_wasted, target, rec_t = _imcr_failure(
                            st, part, failed, phi, matvec, precond, b,
                            dot=dot, fruntime=failure_runtime, tracer=tr)
                    elif strategy == "none":
                        # no redundancy of any kind: nothing can rebuild the
                        # lost entries — cleanly restart from scratch,
                        # counting the work
                        st, ev_wasted, target, rec_t = _none_failure(
                            st, matvec, precond, b, dot=dot)
                    else:
                        (st, ev_wasted, target, ev_inner, rec_t, ev_pff,
                         ev_reload, ev_src, ev_inner_iters) = _esrp_failure(
                            problem, plan, st, failed, T, ops, pff_precond,
                            fruntime=failure_runtime, push=push,
                            n_slabs=qsum_slabs, b=b, tracer=tr)
                        inner_rel = ev_inner
                        push_ranges.append((ev.iter, ev.iter + 1))  # prelude push
                        if target >= 0:
                            ev_fetch = tier.fetch_bytes(
                                max(1, nbatch) * len(failed) *
                                part.rows_per_node, itemsize)
                            ev_fetch_s = tier.read_s(ev_fetch)
                    if batched:
                        msel = (imcr.member_select if strategy == "imcr"
                                else esrp.member_select)
                        st = msel(st_pre, st, done_pre)
                    recovery_s += rec_t
                    wasted += ev_wasted
                    er = EventReport(
                        iter=ev.iter, nodes=ev.nodes, target_iter=target,
                        wasted_iters=ev_wasted, recovery_s=rec_t,
                        inner_rel=ev_inner, pff_iters=ev_pff,
                        inner_iters=ev_inner_iters,
                        precond_reload_bytes=ev_reload, queue_src_nodes=ev_src,
                        tier=tier.name, fetch_bytes=ev_fetch,
                        fetch_s_model=ev_fetch_s)
                    if elastic:
                        # no replacement node exists: re-partition the problem
                        # onto the surviving count and rebuild everything
                        # layout-bound (ops, plan, thresholds); the recovered
                        # state extends with exactly-consistent zero padding
                        # rows (core.elastic)
                        n_new = part.n_nodes - len(ev.nodes)
                        with span("elastic_repartition", "recovery", tr,
                                  n_nodes=n_new):
                            problem = elastic_mod.shrink_problem(problem, n_new)
                            part = problem.part
                            st = elastic_mod.remap_state(st, part.m,
                                                         part.n_nodes)
                            ops = problem.solver_ops(
                                backend, batch=nbatch,
                                fused=batched and batch_fused)
                            matvec, precond = ops.matvec, ops.precond
                            dot = getattr(ops, "dot", None)
                            # the solved RHS (incl. any rhs= override and the
                            # batched (B, M) rows) extends with the same
                            # decoupled-identity zero padding as the state —
                            # NOT problem.b, which would drop the override
                            b = elastic_mod._extend(b, part.m)
                            bnorm = float(jnp.linalg.norm(b))
                            if batched:
                                bnorm_v = _vec_norm(b)
                                thresh_dev = jnp.where(
                                    bnorm_v > 0, rtol * bnorm_v,
                                    jnp.inf).astype(b.dtype)
                                thresh = np.asarray(thresh_dev)
                            else:
                                thresh_dev = jnp.asarray(rtol * bnorm,
                                                         b.dtype)
                                thresh = float(thresh_dev)
                            plan = shrink_plan(plan, problem.a, part)
                            per_push = tier.push_bytes(plan, part.m, itemsize)
                            if qsum_slabs:
                                qsum_slabs = part.n_nodes
                            er.elastic_n_nodes = n_new
                        # the run/resume closures read ops/b/thresh_dev
                        # late-bound — rebinding the locals above re-targets
                        # them to the shrunk layout
                    if tr is not None:
                        if ev_fetch:
                            tr.add_counter("tier_fetch_bytes", ev_fetch,
                                           tier=tier.name)
                        ev_sp.args.update(
                            target_iter=target, wasted_iters=ev_wasted,
                            recovery_s=rec_t, fetch_bytes=ev_fetch)
                    event_reports.append(er)
                    total_iters = int(st.pcg.j)
                    resume_numeric_only = target >= 0
                er.event_s = ev_sp.dur_s
                resumed = (er, ev_sp) if resume_numeric_only else None

        if at_check:
            sdc_checks += 1
            with span("sdc_check", "sdc", tr,
                      iter=total_iters, from_halt=from_halt) as ck_sp:
                # converged members are excluded from detection: their B=1
                # reference runs already ended, so nothing about them may
                # fire a repair (zero-RHS padding is excluded inside
                # run_checks itself)
                live = (~(np.asarray(_vec_norm(st.pcg.r)) < thresh)
                        if batched else None)
                det = sdc.run_checks(ops, st, b, part, bnorm, sdc_policy,
                                     live=live)
                ck_sp.args["fired"] = det is not None
            if det is None and from_halt:
                guard_skip = True
            if det is not None:
                sdc_repairs += 1
                if sdc_repairs > sdc_policy.max_repairs:
                    raise RuntimeError(
                        f"SDC repair fired {sdc_repairs} times without "
                        f"clearing the invariant violation "
                        f"({det.detector}: {det.violation:.3e} > "
                        f"{det.tol:.3e}) — corruption outside the "
                        f"recoverable state, or tolerances below the "
                        f"solver's noise floor")
                # detection-latency attribution: the oldest injection this
                # detector class can see (queue checksums see only queue
                # corruption; the state invariants see everything else)
                want_q = det.detector == "queue-checksum"
                attr = [i for i, tg in sdc_wait if (tg == "queue") == want_q]
                sdc_wait = [(i, tg) for i, tg in sdc_wait
                            if (tg == "queue") != want_q]
                latency = total_iters - attr[0] if attr else -1
                J = int(st.pcg.j)
                if tr is not None:
                    tr.instant("sdc_detect", cat="sdc",
                               detector=det.detector, iter=J,
                               latency=latency,
                               violation=float(det.violation),
                               tol=float(det.tol))
                ev_inner = float("nan")
                ev_pff = -1
                ev_inner_iters = 0
                rec_t = 0.0
                ev_wasted = 0
                ev_src = ()
                ev_fetch = 0
                ev_fetch_s = 0.0
                with span("event:sdc-repair", "event", tr, iter=J,
                          detector=det.detector,
                          nodes=list(det.flagged)) as rp_sp:
                    # converged members are shielded from the rollback
                    # (their reference runs ended before this repair);
                    # queue invalidation is shared bookkeeping (slot axis)
                    # and needs no per-member select
                    st_pre = st if batched else None
                    done_pre = (_vec_norm(st.pcg.r) < thresh_dev) \
                        if batched else None
                    if want_q:
                        # the corrupted copies ARE the redundancy — nothing
                        # can rebuild them; invalidate their slot so no
                        # recovery ever reads them (the next push refreshes
                        # the queue). The live trajectory is untouched:
                        # queue corruption never feeds forward.
                        st = _invalidate_queue_slots(st, det)
                        target = J
                    elif strategy == "none":
                        st, ev_wasted, target, rec_t = _none_failure(
                            st, matvec, precond, b, dot=dot)
                    elif len(det.flagged) >= part.n_nodes:
                        # catastrophic (all slabs non-finite): no survivors
                        # to reconstruct from — restart clean
                        st = esrp.esrp_init(matvec, precond, b, dot=dot,
                                            n_slabs=qsum_slabs)
                        if failure_runtime is not None:
                            st = failure_runtime.init_queue(st, reset=True)
                        ev_wasted, target = J, -1
                    else:
                        (st, ev_wasted, target, ev_inner, rec_t, ev_pff, _,
                         ev_src, ev_inner_iters) = _esrp_failure(
                            problem, plan, st, list(det.flagged), T, ops,
                            pff_precond, fruntime=failure_runtime, push=push,
                            sdc_mode=True, n_slabs=qsum_slabs, b=b,
                            tracer=tr)
                        inner_rel = ev_inner
                        if target >= 0:
                            ev_fetch = tier.fetch_bytes(
                                max(1, nbatch) * len(det.flagged) *
                                part.rows_per_node, itemsize)
                            ev_fetch_s = tier.read_s(ev_fetch)
                    if batched and not want_q:
                        st = esrp.member_select(st_pre, st, done_pre)
                    recovery_s += rec_t
                    wasted += ev_wasted
                    if tr is not None:
                        if ev_fetch:
                            tr.add_counter("tier_fetch_bytes", ev_fetch,
                                           tier=tier.name)
                        rp_sp.args.update(target_iter=target,
                                          wasted_iters=ev_wasted,
                                          latency=latency)
                    er = EventReport(
                        iter=J, nodes=tuple(det.flagged), target_iter=target,
                        wasted_iters=ev_wasted, recovery_s=rec_t,
                        inner_rel=ev_inner, pff_iters=ev_pff,
                        inner_iters=ev_inner_iters,
                        queue_src_nodes=ev_src, kind="sdc-repair",
                        detector=det.detector, detect_iter=J,
                        detect_latency=latency, sdc_violation=det.violation,
                        sdc_tol=det.tol, tier=tier.name, fetch_bytes=ev_fetch,
                        fetch_s_model=ev_fetch_s)
                    event_reports.append(er)
                    total_iters = int(st.pcg.j)
                    resume_numeric_only = (not want_q) and target >= 0
                er.event_s = rp_sp.dur_s
                resumed = (er, rp_sp) if resume_numeric_only else None
    if tr is not None:
        solve_sp.args.update(converged=converged, iters=total_iters,
                             recovery_s=recovery_s, wasted_iters=wasted,
                             run_calls=run_calls)

    with span("epilogue", "solver", tr):
        pcg = st.pcg
        jax.block_until_ready(pcg.x)
        nat_bytes = tot_bytes = 0
        if plan is not None:
            nat_bytes, tot_bytes = plan.bytes_per_aspmv(itemsize)
        push_count = 0
        if strategy == "esrp" and plan is not None:
            push_count = _count_pushes(push_ranges, T)
        if sdc_guard:
            # the guard evaluated one on-device check at every boundary the
            # executed stretches crossed; host confirmations (halts, post-inject
            # checks) were counted live into sdc_checks above
            sdc_checks += _count_checks(push_ranges, sdc_policy.check_every, T,
                                        strategy == "esrp")
        common = dict(
            strategy=strategy, T=T, phi=phi,
            runtime_s=0.0,                 # _in_solve_span sets it once closed
            prologue_s=pro_sp.dur_s,
            recovery_s=recovery_s, wasted_iters=wasted, target_iter=target,
            inner_rel=inner_rel, aspmv_natural_bytes=nat_bytes,
            aspmv_total_bytes=tot_bytes, run_calls=run_calls,
            events=event_reports,
            precond_variant=getattr(ops, "variant", ""),
            spmv_format=ops.spmv_format, spmv_diagonals=ops.spmv_diagonals,
            precond_reload_bytes=sum(e.precond_reload_bytes
                                     for e in event_reports),
            tier=tier.name, push_count=push_count,
            push_bytes=push_count * per_push,
            push_s_model=push_count * (tier.write_s(per_push) if per_push
                                       else 0.0),
            fetch_s_model=sum(e.fetch_s_model for e in event_reports),
            sdc_checks=sdc_checks,
            sdc_check_every=sdc_policy.check_every if sdc_on else 0,
            final_n_nodes=part.n_nodes, trace=tr)
        if not batched:
            drift = float(residual_drift(matvec, b, pcg.x, pcg.r))
            rel = float(jnp.linalg.norm(pcg.r)) / float(jnp.linalg.norm(b))
            report = SolveReport(converged_iter=total_iters, rel_residual=rel,
                                 drift=drift, converged=converged, x=pcg.x,
                                 **common)
            return report
        # batched: one SolveReport per member. Shared run accounting (runtime,
        # events, tier/push totals) repeats on every member — per-member fields
        # are the convergence count, residuals, drift, and the iterate itself.
        rel_v = np.asarray(_vec_norm(pcg.r))
        bn_v = np.asarray(_vec_norm(b))
        drift_v = np.asarray(residual_drift(matvec, b, pcg.x, pcg.r))
        reports = []
        for k in range(nbatch):
            ok = conv_iter[k] >= 0
            reports.append(SolveReport(
                converged_iter=int(conv_iter[k]) if ok else total_iters,
                rel_residual=(float(rel_v[k] / bn_v[k]) if bn_v[k] > 0 else 0.0),
                drift=float(drift_v[k]), converged=bool(ok or converged),
                batch_index=k, batch_size=nbatch, x=pcg.x[k], **common))
        return reports


# --------------------------------------------------------------------------- #
def _at_sdc_boundary(j: int, check_every: int, T: int,
                     esrp_storage: bool) -> bool:
    """Is iteration count ``j`` an invariant-check point? The cadence, plus
    (ESRP) every storage iteration: a check right before each push/star
    commit guarantees the queue and the rollback anchor only ever hold
    verified state — which is what makes a later rollback-based repair
    sound."""
    if j % check_every == 0:
        return True
    return esrp_storage and j > 2 and (j % T == 0 or (j - 1) % T == 0)


def _next_sdc_boundary(j: int, check_every: int, T: int,
                       esrp_storage: bool) -> int:
    """Smallest check boundary strictly greater than ``j``."""
    nxt = (j // check_every + 1) * check_every
    if esrp_storage:
        for k in range(j + 1, nxt):
            if k > 2 and (k % T == 0 or (k - 1) % T == 0):
                return k
    return nxt


def _count_pushes(ranges: list[tuple[int, int]], T: int) -> int:
    """Replay the Alg. 3 storage schedule over the executed iteration
    stretches (rollback re-executes a stretch, so its pushes physically
    happen again)."""
    c = 0
    for base, end in ranges:
        for j in range(base, end):
            if j > 2 and (T == 1 or j % T == 0 or (j - 1) % T == 0):
                c += 1
    return c


def _count_checks(ranges: list[tuple[int, int]], check_every: int, T: int,
                  esrp_storage: bool) -> int:
    """Replay the invariant-check boundaries the on-device guard evaluated
    over the executed iteration stretches (guard mode runs the checks inside
    the scan, so the host loop never sees them — this recovers the
    ``sdc_checks`` accounting host mode counts directly)."""
    c = 0
    for base, end in ranges:
        for j in range(base, end):
            if j > 0 and _at_sdc_boundary(j, check_every, T, esrp_storage):
                c += 1
    return c


def _inject_sdc(problem: Problem, st: esrp.ESRPState, ev: SDCEvent, T: int,
                solver_ops, b, rr_every: int, gated: bool, push):
    """Execute iteration ``ev.iter`` with silent corruption struck
    mid-iteration. The storage prelude runs first and is CLEAN (the paper's
    injection point is right after the ASpMV — the push already carried the
    uncorrupted p), then the corruption lands:

      p/r/x/queue: flipped before the numeric update — the corrupted values
        feed this very iteration and silently propagate (queue corruption
        touches only the stored copy; the trajectory is unaffected).
      z: the carried z is recomputed and consumed into p = z + β·p_prev
        within the same fused update, so a plain pre-step flip of z would
        be a dead store and never observable. The physical event modeled is
        a flip landing between z's computation and its use: run the step
        cleanly, then apply the flip to z and its additive image to p.
    """
    st = jax.jit(esrp.esrp_prelude, static_argnums=(1, 2, 3))(st, T, True,
                                                              push)
    if ev.target == "z":
        st = st._replace(pcg=_resume_step(st.pcg, solver_ops, b, rr_every,
                                          gated))
        st = sdc.corrupt(st, ev, problem.part)
    else:
        st = sdc.corrupt(st, ev, problem.part)
        st = st._replace(pcg=_resume_step(st.pcg, solver_ops, b, rr_every,
                                          gated))
    return st


def _invalidate_queue_slots(st: esrp.ESRPState, det) -> esrp.ESRPState:
    """Queue-checksum repair: drop every slot holding a corrupted copy
    (tag := -1), zeroing its payload and checksums so later checks see a
    consistent empty slot. ``recovery_point`` will fall back to an older
    consecutive pair, or report unrecoverable until the next push."""
    for slot in sorted(set(det.queue_slots) | set(det.rq_slots)):
        st = st._replace(q=st.q.at[slot].set(0.0),
                         q_tags=st.q_tags.at[slot].set(-1))
        if not isinstance(st.q_sums, tuple):
            st = st._replace(q_sums=st.q_sums.at[slot].set(0.0))
        if not isinstance(st.rq, tuple):
            st = st._replace(rq=st.rq.at[slot].set(0.0))
            if not isinstance(st.rq_sums, tuple):
                st = st._replace(rq_sums=st.rq_sums.at[slot].set(0.0))
    return st


# --------------------------------------------------------------------------- #
def _none_failure(st: esrp.ESRPState, matvec, precond, b, dot=None):
    """strategy="none": no redundant copies, no checkpoints — every failure
    is a full restart with target_iter = -1 and J wasted iterations."""
    J = int(st.pcg.j)
    return esrp.esrp_init(matvec, precond, b, dot=dot), J, -1, 0.0


# --------------------------------------------------------------------------- #
def _esrp_failure(problem: Problem, plan: RedundancyPlan, st: esrp.ESRPState,
                  failed: list[int], T: int, solver_ops,
                  pff_precond: bool = True, fruntime=None, push=None,
                  sdc_mode: bool = False, n_slabs: int = 0, b=None,
                  tracer=None):
    """Failure strikes during iteration J right after its (A)SpMV: run the
    iteration-J storage prelude (including, on the sharded runtime, the
    physical redundancy sends that were already in flight), lose the failed
    nodes' dynamic data, then reconstruct (Alg. 2) and rebuild a consistent
    post-stage ESRP state.

    With ``fruntime`` (comm.shard.ShardedFailureRuntime) the whole failure
    path is device-resident: injection is a shard_map zeroing of the failed
    devices' shards only, and the p^(j-1)/p^(j) copies feeding Alg. 2 are
    read out of the *surviving devices'* queue shards (``ESRPState.rq``),
    never from a replicated array. Without it (the single-device simulator)
    the queue is the host-visible (3, M) array and injection is the
    replicated ``jnp.where`` of the paper's simulation protocol.

    ``sdc_mode`` repurposes the same machinery for detected silent
    corruption: nothing was physically lost — the flagged nodes' *live*
    vectors are untrustworthy, but their queue copies, held redundancy
    shards, and static data are intact (the check-before-store protocol
    plus read-time checksums guarantee it). So: no storage prelude (pushing
    the corrupted p would poison the queue), the discard zeroes live +
    starred state only, the redundancy survival analysis is skipped, and
    the p-pair reads straight from the host-visible queue. The rollback
    then discards EVERY live vector — survivors restore from the (clean)
    stars, flagged rows rebuild via Alg. 2 — so repair correctness never
    depends on how precisely the detector localized the corruption.
    """
    part = problem.part
    matvec, precond = solver_ops.matvec, solver_ops.precond
    # b: the RHS actually being solved (the batched driver passes its
    # (B, M) rhs; None keeps problem.b — the unbatched default)
    b_rhs = problem.b if b is None else b
    J = int(st.pcg.j)
    if not sdc_mode:
        with span("prelude", "recovery", tracer):
            st = jax.jit(esrp.esrp_prelude, static_argnums=(1, 2, 3))(
                st, T, True, push)

    # --- the failure: all dynamic data on failed nodes is lost -------------
    with span("inject", "recovery", tracer, nodes=list(failed),
              sdc_mode=sdc_mode):
        if sdc_mode and fruntime is not None:
            st = fruntime.lose_live(st, failed)
            reload_bytes = 0
        elif fruntime is not None:
            st = fruntime.lose_esrp(st, failed)
            reload_desc, reload_bytes = fruntime.precond_reload(failed)
            del reload_desc
        else:
            mask = failed_row_mask(part, failed)
            lose = lambda v: zero_failed(v, mask)
            pcg = st.pcg._replace(x=lose(st.pcg.x), r=lose(st.pcg.r),
                                  z=lose(st.pcg.z), p=lose(st.pcg.p))
            st = st._replace(pcg=pcg, x_s=lose(st.x_s), r_s=lose(st.r_s),
                             z_s=lose(st.z_s), p_s=lose(st.p_s))
            reload_bytes = 0
    pcg = st.pcg

    if not sdc_mode:
        # per-event φ-copy survival analysis: a redundant copy of every
        # failed tile must outlive this event's failed set (topology-aware,
        # so a lucky |failed| > φ set can still pass — see
        # RedundancyPlan.check_event). SDC loses no copies — skip.
        plan.check_event(failed)

    target, prev_slot, curr_slot = esrp.recovery_point(st, T)
    if target < 0:
        # before the first completed storage stage: restart from scratch
        st2 = esrp.esrp_init(matvec, precond, b_rhs, dot=solver_ops.dot,
                             n_slabs=n_slabs)
        if fruntime is not None:
            st2 = fruntime.init_queue(st2, reset=True)
        return st2, J, -1, float("nan"), 0.0, -1, reload_bytes, (), 0

    if T == 1:
        # ESR: no rollback — reconstruct the *live* iteration J from the
        # surviving r, x and the replicated scalar β^(J-1) (paper §2.3)
        r_surv, x_surv, z_surv, p_surv = pcg.r, pcg.x, pcg.z, pcg.p
        beta_prev = pcg.beta
        rz = pcg.rz          # replicated scalar — survives the failure
    else:
        r_surv, x_surv, z_surv, p_surv = st.r_s, st.x_s, st.z_s, st.p_s
        beta_prev = st.beta_s
        # r*ᵀz* was captured with the stars precisely so the rollback needs
        # no recompute from the (partly reconstructed) vectors: the stored
        # scalar is the exact value of the uncorrupted trajectory.
        rz = st.rz_s

    # the redundant p-copies Alg. 2 reads: on the sharded runtime the failed
    # rows are assembled from the surviving devices' physical queue shards
    # (the injection zeroed the failed rows of ``q`` itself); the simulator
    # reads the host-side queue directly. In sdc_mode nothing was wiped —
    # every node's own queue rows are intact and were checksum-verified by
    # this very check pass (the queue detector runs first), so the pair
    # reads straight from ``q`` on both runtimes.
    fetch_bytes = 2 * len(failed) * part.rows_per_node * \
        np.dtype(problem.b.dtype).itemsize * \
        (b_rhs.shape[0] if b_rhs.ndim == 2 else 1)
    with span("queue_fetch", "recovery", tracer,
              slots=[int(prev_slot), int(curr_slot)],
              bytes=int(fetch_bytes)) as qf_sp:
        if fruntime is not None and not sdc_mode:
            p_prev, p_curr, src_nodes = fruntime.assemble_pair(
                st, prev_slot, curr_slot, failed)
        else:
            p_prev, p_curr, src_nodes = st.q[prev_slot], st.q[curr_slot], ()
        if tracer is not None:
            jax.block_until_ready(p_curr)
            qf_sp.args["sources"] = list(src_nodes)

    # static-data reload (excluded from the recovery timing, paper §4) —
    # cached per (problem, failed-set) so repeated benchmark runs also reuse
    # the jitted inner solve (a C framework has no JIT warmup; timing it
    # would misattribute compilation to the paper's reconstruction cost)
    cache = getattr(problem, "_recon_cache", None)
    if cache is None:
        cache = {}
        problem._recon_cache = cache
    key = (tuple(failed), pff_precond)
    if key not in cache:
        with span("reconstruction_build", "build", tracer,
                  nodes=list(failed), pff_precond=pff_precond):
            ops = esr.ReconstructionOps.build(problem, failed,
                                              pff_precond=pff_precond)
            bf_warm = (None if b is None
                       else b_rhs[..., jnp.asarray(ops.f_rows)])
            # warm the jitted reconstruction (compile excluded from timing)
            esr.reconstruct(ops, p_prev=p_prev, p_curr=p_curr,
                            beta_prev=beta_prev, r_surv=r_surv,
                            x_surv=x_surv, b_f=bf_warm
                            )[0].block_until_ready()
        cache[key] = ops
    ops = cache[key]
    b_f = None if b is None else b_rhs[..., jnp.asarray(ops.f_rows)]
    # recovery_s: Alg. 2 and the scatter, up to the rebuilt x on the device
    with span("reconstruct", "recovery", tracer,
              target_iter=target) as rec_sp:
        x_f, r_f, z_f, inner_rel, inner_iters = esr.reconstruct(
            ops, p_prev=p_prev, p_curr=p_curr, beta_prev=beta_prev,
            r_surv=r_surv, x_surv=x_surv, b_f=b_f, tracer=tracer)
        with span("scatter", "recovery", tracer, target_iter=target):
            f_rows = jnp.asarray(ops.f_rows)
            x = x_surv.at[..., f_rows].set(x_f)
            r = r_surv.at[..., f_rows].set(r_f)
            z = z_surv.at[..., f_rows].set(z_f)
            p = p_surv.at[..., f_rows].set(p_curr[..., f_rows])
            jax.block_until_ready(x)

    new_pcg = PCGState(x=x, r=r, z=z, p=p, rz=rz, beta=beta_prev,
                       j=jnp.asarray(target, jnp.int32))
    empty = jnp.zeros_like(p)
    st2 = esrp.ESRPState(
        pcg=new_pcg,
        q=jnp.stack([empty, p_prev, p_curr]),
        q_tags=jnp.asarray([-1, target - 1, target], jnp.int32),
        x_s=x, r_s=r, z_s=z, p_s=p, beta_s=beta_prev, rz_s=rz,
        star_tag=jnp.asarray(target, jnp.int32))
    if not isinstance(st.q_sums, tuple):
        nsl = st.q_sums.shape[-1]
        # failed slabs were rebuilt (their content is fresh — recompute);
        # surviving slabs keep their STORED push-time checksums, so a copy
        # corrupted before this event keeps failing its checksum after the
        # restack instead of being laundered into a consistent one.
        # Batched: the per-member (B, nsl) rows broadcast against the
        # (nsl,) failed-slab mask — the failed node set is shared across
        # members (one event strikes every member's rows)
        fmask = jnp.zeros((nsl,), bool).at[jnp.asarray(failed)].set(True)
        st2 = st2._replace(q_sums=jnp.stack([
            jnp.zeros_like(st.q_sums[0]),
            jnp.where(fmask, sdc.slab_sums(p_prev, nsl),
                      st.q_sums[prev_slot]),
            jnp.where(fmask, sdc.slab_sums(p_curr, nsl),
                      st.q_sums[curr_slot])]))
    if fruntime is not None:
        # survivors keep their physical copies; the replacement's shard
        # stays empty (it was wiped) until the next storage push refreshes
        # every device's entry — tracked so a burst event cannot silently
        # read a stale copy. (sdc_mode: nothing was wiped — every holder's
        # copy is intact and stays readable.)
        st2 = st2._replace(rq=jnp.stack(
            [jnp.zeros_like(st.rq[0]), st.rq[prev_slot], st.rq[curr_slot]]))
        if not isinstance(st.rq_sums, tuple):
            st2 = st2._replace(rq_sums=jnp.stack(
                [jnp.zeros_like(st.rq_sums[0]), st.rq_sums[prev_slot],
                 st.rq_sums[curr_slot]]))
        if not sdc_mode:
            fruntime.mark_wiped(failed, target)
    pff_stats = getattr(ops.p_solve, "stats", None) if ops.p_solve else None
    pff_iters = pff_stats["iters"] if pff_stats else -1
    # batched line-8 rel is per-member — report the worst one
    inner_rel, inner_iters = jax.device_get((inner_rel, inner_iters))
    return (st2, J - target, target, float(np.max(inner_rel)),
            rec_sp.dur_s, pff_iters, reload_bytes, src_nodes,
            int(np.max(inner_iters)))


def _imcr_failure(st: imcr.IMCRState, part, failed: list[int], phi: int,
                  matvec, precond, b, dot=None, fruntime=None, tracer=None):
    """IMCR: zero the failed nodes' live data, then everyone rolls back to the
    last checkpoint (replacements fetch their parts from surviving buddies).

    The checkpoint state (``ck_*``, ``ck_tag``) is left untouched by
    recovery: it still holds the rolled-back-to iteration, so a *second*
    event striking before the next scheduled checkpoint finds a valid
    anchor and rolls back to the same tag again."""
    J = int(st.pcg.j)
    # per-event buddy-survival analysis (|failed| ≤ φ always passes; a
    # spread-out larger set may too — see imcr.check_survivable)
    imcr.check_survivable(failed, phi, part.n_nodes)
    if fruntime is not None:
        # sharded runtime: zero only the failed devices' shards (shard_map)
        st = st._replace(pcg=fruntime.lose_pcg(st.pcg, failed))
    else:
        mask = failed_row_mask(part, failed)
        lose = lambda v: zero_failed(v, mask)
        st = st._replace(pcg=st.pcg._replace(
            x=lose(st.pcg.x), r=lose(st.pcg.r), z=lose(st.pcg.z),
            p=lose(st.pcg.p)))
    tag = int(st.ck_tag)
    if tag < 0:                      # failure before the first checkpoint
        return imcr.imcr_init(matvec, precond, b, dot=dot), J, -1, 0.0
    with span("buddy_restore", "recovery", tracer, tag=tag,
              nodes=list(failed)) as rs_sp:
        pcg = imcr.recover(st)       # fetch-from-buddy (restore the copies)
        jax.block_until_ready(pcg.x)
    return st._replace(pcg=pcg), J - tag, tag, rs_sp.dur_s
