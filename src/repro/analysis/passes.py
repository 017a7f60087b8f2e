"""The five static-invariant passes.

Each pass takes an ``EntrySpec`` (a traced entry-point jaxpr plus the
invariants the entry registered for) and returns ``Finding`` records.
Passes are pure jaxpr walks — no jax import, no execution — so a full
``--entry all`` run costs tracing time only.

  identity     structural differ vs the entry's registered reference jaxpr
               (obs=off / sdc_policy=None must compile to the pre-telemetry,
               guard-free chunk runner — exact rejoin rests on it)
  gating       every ``cond`` gate must own a work-free branch: the disabled
               side of the storage/SDC/residual-replacement gates adds zero
               SpMV/dot/queue-copy ops on non-storage iterations
  host_sync    no device->host forcing op (callbacks, infeed/outfeed) inside
               chunk bodies registered sync-free
  determinism  full-contraction reductions on bit-identical paths must be
               pinned by the optimization_barrier partial-accumulation idiom;
               batched entries must never reduce across the member axis
  sharding     shard_map in/out names stay on the declared mesh axes with no
               unintended replication, member-axis sharding, or explicit
               all-gathers beyond the entry's known SpMV-gather budget
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

from repro.analysis import structural, walker
from repro.analysis.findings import Finding

PASS_IDS = ("identity", "gating", "host_sync", "determinism", "sharding")

# ops that do real work when they appear inside a gate's "disabled" branch:
# SpMV/dot arithmetic, nested loops, and the queue-copy data movement the
# storage prelude performs on push iterations
WORK_PRIMS = frozenset({
    "dot_general", "conv_general_dilated", "scan", "while", "pallas_call",
    "concatenate", "gather", "scatter", "scatter-add", "dynamic_slice",
    "dynamic_update_slice", "all_gather", "ppermute", "psum", "all_reduce",
})

# primitives that force a device->host transfer (or round-trip) when they
# appear inside a chunk body: the sync-free driver protocol forbids them
SYNC_PRIM_NAMES = frozenset({
    "infeed", "outfeed", "debug_print", "host_local_array_to_global_array",
    "global_array_to_host_local_array",
})

# producers whose scalar reduce_sum is a *norm* (abs/square chains): these
# are shared jnp subgraphs across backends — deterministic by construction,
# no partial-accumulation pinning required (see EXPERIMENTS.md)
_NORM_PRODUCERS = frozenset({"abs", "integer_pow", "square", "real"})
# shape-preserving hops the pin detector looks through between a barrier
# and the reduction it pins
_TRANSPARENT = frozenset({"reshape", "convert_element_type", "squeeze",
                          "transpose", "copy"})


@dataclasses.dataclass
class EntrySpec:
    """One registered entry point: its traced jaxpr plus the invariant
    contract the passes check it against."""
    name: str
    jaxpr: Any                          # ClosedJaxpr of the entry
    tags: frozenset = frozenset()       # {"sync_free","gated","bit_identical",
    #                                      "batched","sharded"}
    identity_ref: Any = None            # ClosedJaxpr the entry must match
    identity_label: str = ""            # what the ref re-derives
    batch: int = 0                      # leading member-axis extent (0 = unbatched)
    min_gates: int = 0                  # cond gates the entry must hoist
    mesh_axes: tuple = ()               # declared mesh axis names ("nodes",)
    allowed_gathers: int | None = None  # explicit all_gather budget
    nodes_axis_by_rank: dict = dataclasses.field(default_factory=dict)
    #                                   # rank -> allowed sharded-axis indices
    repl_limit: int = 256               # max elements a replicated operand may hold


def _f(spec, pass_id, path, code, explanation, severity="error") -> Finding:
    return Finding(pass_id=pass_id, entry=spec.name, eqn_path=path,
                   severity=severity, code=code, explanation=explanation)


# --------------------------------------------------------------------------- #
# pass 1: structural identity
# --------------------------------------------------------------------------- #
def run_identity(spec: EntrySpec) -> list[Finding]:
    if spec.identity_ref is None:
        return []
    div = structural.first_divergence(spec.jaxpr, spec.identity_ref)
    if div is None:
        return []
    return [_f(spec, "identity", div["path"], "jaxpr-divergence",
               structural.divergence_message(div, spec.identity_label))]


# --------------------------------------------------------------------------- #
# pass 2: gating audit
# --------------------------------------------------------------------------- #
def _work_count(jaxpr) -> int:
    return sum(1 for s in walker.walk(jaxpr)
               if s.eqn.primitive.name in WORK_PRIMS)


def run_gating(spec: EntrySpec) -> list[Finding]:
    findings = []
    conds = walker.sites_of(spec.jaxpr, "cond")
    for site in conds:
        branches = walker.cond_branches(site.eqn)
        if len(branches) != 2:
            continue                    # N-way switch, not a gate
        costs = [_work_count(b) for b in branches]
        if min(costs) > 0:
            findings.append(_f(
                spec, "gating", site.path, "gated-branch-not-free",
                f"cond gate has no work-free branch: per-branch work-op "
                f"counts {costs} (WORK_PRIMS) — the disabled side of a "
                f"storage/SDC/replacement gate must contribute zero "
                f"SpMV/dot/queue-copy ops"))
    if spec.min_gates and len(conds) < spec.min_gates:
        findings.append(_f(
            spec, "gating", "", "missing-gates",
            f"entry registered {spec.min_gates} cond gates (storage push, "
            f"star capture, replacement, ...) but only {len(conds)} cond "
            f"eqns found — bookkeeping has been un-hoisted into the "
            f"unconditional trace"))
    return findings


# --------------------------------------------------------------------------- #
# pass 3: host-sync detection
# --------------------------------------------------------------------------- #
def run_host_sync(spec: EntrySpec) -> list[Finding]:
    if "sync_free" not in spec.tags:
        return []
    findings = []
    for site in walker.walk(spec.jaxpr):
        name = site.eqn.primitive.name
        if name in SYNC_PRIM_NAMES or "callback" in name:
            findings.append(_f(
                spec, "host_sync", site.path, "host-sync",
                f"'{name}' forces a device->host transfer inside a chunk "
                f"body registered sync-free — it would stall the driver's "
                f"overlapped dispatch/readback protocol"))
    return findings


# --------------------------------------------------------------------------- #
# pass 4: determinism / re-association lint
# --------------------------------------------------------------------------- #
def _producer_index(jaxpr):
    """var id -> producing eqn, for one (sub-)jaxpr level."""
    idx = {}
    for eqn in walker.unwrap(jaxpr).eqns:
        for v in eqn.outvars:
            idx[id(v)] = eqn
    return idx


def _pinned_or_norm(eqn, producers, hops: int = 4) -> bool:
    """Is this reduction's operand chain pinned by an optimization_barrier,
    or a norm-shaped (abs/square) monitoring reduction?"""
    var = eqn.invars[0]
    for _ in range(hops):
        prod = producers.get(id(var))
        if prod is None:
            return False
        name = prod.primitive.name
        if name in ("optimization_barrier", "pallas_call"):
            # a kernel output is as opaque to XLA as a barrier: the
            # partials' association is fixed at the kernel boundary
            return True
        if name in _NORM_PRODUCERS:
            return True
        if name == "mul" and len(prod.invars) == 2 \
                and prod.invars[0] is prod.invars[1]:
            return True                 # x*x square
        if name not in _TRANSPARENT:
            return False
        var = prod.invars[0]
    return False


def _scalar_contraction(eqn, batch: int) -> bool:
    """A dot_general / reduce_sum collapsing a whole vector: output rank 0,
    or rank 1 of extent ``batch`` (a per-member full contraction)."""
    out = eqn.outvars[0].aval
    shape = getattr(out, "shape", None)
    if shape is None:
        return False
    if len(shape) == 0:
        return True
    return batch > 0 and len(shape) == 1 and shape[0] == batch


def _each_jaxpr(jaxpr, prefix=""):
    """(prefix, jaxpr) for the entry and every nested sub-jaxpr — except
    pallas_call kernel bodies, whose reduction association is fixed by the
    kernel's own grid/block program (the pinning idiom lives *around* the
    kernel, not inside it)."""
    j = walker.unwrap(jaxpr)
    yield prefix, j
    for i, eqn in enumerate(j.eqns):
        if eqn.primitive.name == "pallas_call":
            continue
        for key, sub in walker.sub_jaxprs(eqn):
            yield from _each_jaxpr(sub, f"{prefix}eqn{i}/{key}/")


def run_determinism(spec: EntrySpec) -> list[Finding]:
    findings = []
    if "bit_identical" in spec.tags:
        for prefix, j in _each_jaxpr(spec.jaxpr):
            producers = _producer_index(j)
            for i, eqn in enumerate(j.eqns):
                name = eqn.primitive.name
                if name not in ("dot_general", "reduce_sum"):
                    continue
                if not _scalar_contraction(eqn, spec.batch):
                    continue
                op_shape = getattr(eqn.invars[0].aval, "shape", ())
                if name == "reduce_sum" \
                        and int(_size(op_shape)) <= 16:
                    continue            # tiny bookkeeping reduce
                if name == "dot_general":
                    # the pinned idiom never emits a full-contraction
                    # dot_general — it splits into per-block partials +
                    # barrier + reduce_sum — so any scalar dot here is
                    # an unpinned reduction
                    findings.append(_f(
                        spec, "determinism", f"{prefix}eqn{i}",
                        "unpinned-dot",
                        f"full-contraction dot_general (operand shape "
                        f"{tuple(op_shape)}) on a bit-identical-registered "
                        f"path: XLA may re-associate it per "
                        f"backend/topology — use the per-block partials + "
                        f"optimization_barrier + reduce_sum idiom "
                        f"(kernels/spmv/ref.py)"))
                elif not _pinned_or_norm(eqn, producers):
                    findings.append(_f(
                        spec, "determinism", f"{prefix}eqn{i}",
                        "unpinned-reduce",
                        f"scalar reduce_sum (operand shape "
                        f"{tuple(op_shape)}) not fed by an "
                        f"optimization_barrier (and not a norm-shaped "
                        f"abs/square reduction): the partial-sum "
                        f"association is at XLA's mercy"))
    if spec.batch > 0:
        for site in walker.walk(spec.jaxpr):
            eqn = site.eqn
            name = eqn.primitive.name
            if name not in ("reduce_sum", "reduce_prod",
                            "reduce_max", "reduce_min"):
                continue
            aval = eqn.invars[0].aval
            shape = getattr(aval, "shape", ())
            dtype = str(getattr(aval, "dtype", ""))
            axes = eqn.params.get("axes", ())
            if (len(shape) >= 2 and shape[0] == spec.batch
                    and 0 in tuple(axes) and dtype.startswith("float")):
                findings.append(_f(
                    spec, "determinism", site.path, "batch-axis-reduction",
                    f"{name} over axis 0 of a ({spec.batch}, ...) operand "
                    f"mixes members across the batch axis — batched ops "
                    f"must be rank-polymorphic in the leading axis "
                    f"(reduce per member, axis=-1)"))
    return findings


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


# --------------------------------------------------------------------------- #
# pass 5: sharding-spec check
# --------------------------------------------------------------------------- #
def _spec_to_axis(spec) -> int | None:
    """shard_map in/out PartitionSpec -> index of the dim sharded on the
    mesh (None = fully replicated)."""
    sharded = [d for d, ax in enumerate(spec) if ax]
    return sharded[0] if sharded else None


def run_sharding(spec: EntrySpec) -> list[Finding]:
    if "sharded" not in spec.tags:
        return []
    findings = []
    gathers = 0
    for site in walker.sites_of(spec.jaxpr, "shard_map"):
        eqn = site.eqn
        mesh = eqn.params.get("mesh")
        axis_names = tuple(getattr(mesh, "axis_names", ()))
        if spec.mesh_axes and axis_names != tuple(spec.mesh_axes):
            findings.append(_f(
                spec, "sharding", site.path, "foreign-mesh",
                f"shard_map over mesh axes {axis_names} — entry declared "
                f"{tuple(spec.mesh_axes)}"))
        in_specs = eqn.params.get("in_specs", ())
        out_specs = eqn.params.get("out_specs", ())
        roles = [("in", p, v.aval) for p, v in zip(in_specs, eqn.invars)] + \
                [("out", p, v.aval) for p, v in zip(out_specs, eqn.outvars)]
        for role, pspec, aval in roles:
            shape = tuple(getattr(aval, "shape", ()))
            rank = len(shape)
            axis = _spec_to_axis(pspec)
            if axis is None:
                if _size(shape) > spec.repl_limit:
                    findings.append(_f(
                        spec, "sharding", site.path, "unintended-replication",
                        f"shard_map {role} of shape {shape} is fully "
                        f"replicated ({_size(shape)} elems > repl_limit "
                        f"{spec.repl_limit}): every device pays the whole "
                        f"array — shard it on 'nodes' or whitelist it"))
                continue
            if spec.batch and axis == 0 and rank >= 2 \
                    and shape[0] == spec.batch:
                findings.append(_f(
                    spec, "sharding", site.path, "member-axis-sharded",
                    f"shard_map {role} of shape {shape} shards the leading "
                    f"member axis (B={spec.batch}) across 'nodes' — members "
                    f"are independent solves and must stay device-local "
                    f"(expected P(None, ..., 'nodes'))"))
                continue
            allowed = spec.nodes_axis_by_rank.get(rank)
            if allowed is not None and axis not in allowed:
                findings.append(_f(
                    spec, "sharding", site.path, "wrong-partition-axis",
                    f"shard_map {role} of shape {shape} sharded on axis "
                    f"{axis}; entry declares rank-{rank} operands sharded "
                    f"on axis {tuple(allowed)} (e.g. rq (3,B,n,w,bn) under "
                    f"P(None,None,'nodes'))"))
        body = eqn.params.get("jaxpr")
        if body is not None:
            gathers += sum(1 for s in walker.walk(body)
                           if s.eqn.primitive.name == "all_gather")
    if spec.allowed_gathers is not None and gathers > spec.allowed_gathers:
        findings.append(_f(
            spec, "sharding", "", "extra-all-gather",
            f"{gathers} explicit all_gather eqns inside shard_map bodies; "
            f"entry budgets {spec.allowed_gathers} (the known SpMV halo "
            f"gather + queue retention) — an extra gather replicates a "
            f"whole vector per call"))
    # sharding_constraint specs must stay on the declared mesh axes
    for site in walker.sites_of(spec.jaxpr, "sharding_constraint"):
        sharding = site.eqn.params.get("sharding")
        sp = getattr(sharding, "spec", None)
        if sp is None:
            continue
        used = {a for part in sp if part
                for a in ((part,) if isinstance(part, str) else tuple(part))}
        if spec.mesh_axes and not used <= set(spec.mesh_axes):
            findings.append(_f(
                spec, "sharding", site.path, "foreign-mesh",
                f"with_sharding_constraint uses axes {sorted(used)} outside "
                f"the declared mesh {tuple(spec.mesh_axes)}"))
    return findings


PASSES: dict[str, Callable[[EntrySpec], list[Finding]]] = {
    "identity": run_identity,
    "gating": run_gating,
    "host_sync": run_host_sync,
    "determinism": run_determinism,
    "sharding": run_sharding,
}


def run_passes(spec: EntrySpec, pass_ids=PASS_IDS) -> list[Finding]:
    findings = []
    for pid in pass_ids:
        findings += PASSES[pid](spec)
    return findings
