"""Distributed solver runtime: the paper's MPI cluster on a JAX mesh.

Three layers:

* ``place_problem`` + ``sharded_matvec`` — the production path: block-rows of
  the Block-ELL matrix and all vectors are sharded over a 1-D "nodes" mesh
  axis; the SpMV's halo exchange is an ``all_gather`` of the input vector
  (general sparsity) under ``shard_map``, each device running the
  sequential-k Block-ELL product over its own row slab, and dot products
  reduce as per-node partials + ``psum`` — so the *same* ESRP/IMCR code from
  ``repro.core`` runs distributed unchanged, and ``mesh_mirror_ops`` builds
  the single-device reference bundle with the identical reduction structure
  (the sharded trajectory is bit-identical to it in f64, tested on 8 host
  devices).

* the **device-resident failure story**: ``redundancy_queue`` materializes
  the paper §2.2.1 ASpMV redundancy on the mesh — at every storage push the
  current search direction's column tiles are physically placed on their
  designated holder devices (ring ``ppermute`` sends to the d_{s,k}
  neighbours + retention of the naturally-travelling tiles), rotating
  through the queue-of-3 in ``ESRPState.rq``. ``ShardedFailureRuntime``
  plugs into ``core.driver.solve_resilient``: failure injection is a
  ``shard_map`` operation zeroing only the failed devices' shards (live
  vectors, starred locals, own-queue rows AND the copies the failed device
  held for others), and reconstruction reads p^(j-1), p^(j) for the failed
  rows out of the *surviving devices'* queue shards — never from a
  replicated array — with a device-resident survival check that is stricter
  than the static plan (a copy wiped by an earlier event only revives at
  the next storage push).

* ``ring_halo_matvec`` — the banded-matrix specialization matching the
  paper's point-to-point neighbour sends: each node exchanges only its
  boundary column-tiles with its ±1 ring neighbours via
  ``jax.lax.ppermute`` inside ``shard_map`` (the TPU ICI analogue of the
  paper's MPI sends; ASpMV's designated destinations d_{s,k} are the same
  ring hops). Valid when the sparsity bandwidth fits within one node's
  column range (Poisson-type problems partitioned in slabs).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.marks import sync_free
from repro.sparse.blockell import BlockEll
from repro.sparse.matrices import Problem


def nodes_mesh(n_nodes: int) -> Mesh:
    return jax.make_mesh((n_nodes,), ("nodes",),
                         axis_types=(AxisType.Auto,))


def place_problem(problem: Problem, mesh: Mesh) -> Problem:
    """Shard the static data block-row-wise over the "nodes" axis."""
    a = problem.a
    row_sh = NamedSharding(mesh, P("nodes"))
    vec_sh = NamedSharding(mesh, P("nodes"))
    a2 = BlockEll(jax.device_put(a.data, row_sh),
                  jax.device_put(a.idx, row_sh),
                  jax.device_put(a.nblk, row_sh), a.shape, a.bm, a.bn)
    return dataclasses.replace(
        problem, a=a2, b=jax.device_put(problem.b, vec_sh),
        pinv_blocks=jax.device_put(problem.pinv_blocks, row_sh),
        diag_blocks=jax.device_put(problem.diag_blocks, row_sh))


def sharded_matvec(a: BlockEll, mesh: Mesh, batch: int = 0):
    """General-sparsity distributed SpMV under ``shard_map``: all-gather x
    (the halo exchange), then each device runs the *sequential-k* Block-ELL
    product over its own row slab.

    The per-row accumulation order is exactly ``spmv_seq_ref``'s (the jnp
    SolverOps backend), and rows are independent — so the distributed
    product is bit-identical in f64 to the single-device one regardless of
    how XLA partitions the surrounding graph (the free-form einsum the
    previous implementation used re-associated the k×bn reduction
    differently under SPMD partitioning). ``mesh_mirror_ops`` relies on
    this for the single-device reference trajectory.

    ``batch`` > 0: the input is (B, M) with the row axis sharded
    (P(None, "nodes")); ONE all-gather moves every member's halo and each
    device runs the per-member-unrolled sequential product over its slab —
    per member bit-identical to the unbatched sharded product.
    """
    mv = _sharded_spmv(mesh, batch)
    return lambda x: mv(a.data, a.idx, x)


def _sharded_spmv(mesh: Mesh, batch: int = 0):
    """``sharded_matvec``'s product as (data, idx, x) -> y."""
    from repro.kernels.spmv.ref import spmv_seq_ref

    if batch:
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("nodes"), P("nodes"), P(None, "nodes")),
            out_specs=P(None, "nodes"), check_vma=False)
        def mv_b(data, idx, x_local):
            xg = jax.lax.all_gather(x_local, "nodes", axis=1, tiled=True)
            return jnp.stack([spmv_seq_ref(data, idx, xg[i])
                              for i in range(batch)])

        return mv_b

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("nodes"), P("nodes"), P("nodes")),
        out_specs=P("nodes"), check_vma=False)
    def mv(data, idx, x_local):
        xg = jax.lax.all_gather(x_local, "nodes", tiled=True)
        return spmv_seq_ref(data, idx, xg)

    return mv


def _dot_lane(m: int, n_nodes: int, lane: int = 8) -> int:
    """Lane width for the pinned slab dot (the f64 SIMD register width; the
    Block-ELL bn in practice). Falls back to 1 when the slab doesn't tile."""
    slab = m // n_nodes
    return lane if slab % lane == 0 else 1


def _slab_dot(u, v, lane: int):
    """One node's share of a distributed dot, with a *pinned* reduction
    structure: per-``lane``-wide row partials (a fixed-size SIMD reduce XLA
    cannot re-associate) barriered against collapsing, then one flat sum of
    the row partials. A plain local ``u @ v`` compiles to a different
    re-association depending on the surrounding fusion context, which breaks
    the sharded-vs-mirror bit-identity (measured: ~half of random inputs)."""
    p = jnp.einsum("rj,rj->r", u.reshape(-1, lane), v.reshape(-1, lane))
    return jnp.sum(jax.lax.optimization_barrier(p))


def sharded_dot(mesh: Mesh, m: int, lane: int = 8, batch: int = 0):
    """uᵀv for node-sharded vectors: each device reduces its own slab with
    the pinned structure of ``_slab_dot``, then ``psum`` accumulates the
    per-node partials around the ring (sequential order — ``mesh_dot`` is
    the bit-identical single-device form).

    ``batch`` > 0 takes (B, M) inputs and returns the (B,) replicated dot
    vector: per-member pinned slab reductions (the exact unbatched
    subgraph, unrolled) stacked into one psum."""
    n = mesh.shape["nodes"]
    lane = _dot_lane(m, n, lane)

    if batch:
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(None, "nodes"), P(None, "nodes")),
                           out_specs=P(), check_vma=False)
        def dot_b(u, v):
            part = jnp.stack([_slab_dot(u[i], v[i], lane)
                              for i in range(batch)])
            return jax.lax.psum(part, "nodes")

        return dot_b

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("nodes"), P("nodes")), out_specs=P(),
                       check_vma=False)
    def dot(u, v):
        return jax.lax.psum(_slab_dot(u, v, lane), "nodes")

    return dot


def mesh_dot(n_nodes: int, m: int, lane: int = 8):
    """Single-device uᵀv with the mesh's exact reduction structure: the same
    pinned per-slab dots as ``sharded_dot``'s shard_map body, accumulated
    sequentially over the node axis like ``psum`` does around the ring —
    bit-identical in f64 to the distributed dot (``mesh_mirror_ops``)."""
    lane = _dot_lane(m, n_nodes, lane)

    def dot(u, v):
        u8 = u.reshape(n_nodes, -1)
        v8 = v.reshape(n_nodes, -1)
        acc = jnp.zeros((), u.dtype)
        for s in range(n_nodes):
            acc = acc + _slab_dot(u8[s], v8[s], lane)
        return acc

    return dot


def _ensure_node_local(problem: Problem, n: int):
    """Adopt the node-local (additive-Schwarz) twin problem-wide when the
    registered SSOR/IC(0) instance still carries cross-slab coupling, so
    that Alg. 2 recovery reconstructs against the same operator the
    distributed hot loop applies. Clears every closure cache bound to the
    replaced global-sweep operator — including ``_sharded_ops_cache``: a
    same-shape mesh entry built pre-adoption would otherwise keep applying
    the old operator (``jax.make_mesh`` interns equal-shape meshes, so the
    stale entry is reachable from a *fresh* mesh object)."""
    from repro.precond import local as plocal

    pc = problem.precond
    if plocal.precond_is_node_local(pc, n):
        return pc, False
    pc = plocal.node_local_twin(problem)
    problem.precond = pc
    for attr in ("_recon_cache", "_ops_cache", "_closure_ops_cache",
                 "_sharded_ops_cache", "_mesh_mirror_cache"):
        if hasattr(problem, attr):
            delattr(problem, attr)
    assert plocal.precond_is_node_local(pc, n)
    return pc, True


def _sharded_sweep_precond(problem: Problem, mesh: Mesh):
    """Node-local SSOR/IC(0) apply for the sharded runtime.

    The sweeps run under ``shard_map`` with every static strip placed
    block-row-wise: each device substitutes through *its own* diagonal slab
    only — the additive-Schwarz variant, embarrassingly parallel over the
    "nodes" axis (a global sequential sweep would serialize the whole
    distributed iteration). If the problem's preconditioner still carries
    cross-slab coupling, its node-local twin is built from the COO in safe
    storage and **adopted as ``problem.precond``** so that Alg. 2 recovery
    reconstructs against the same operator the hot loop applies.
    Per-row arithmetic matches the single-device node-local reference
    (``build_problem(..., precond_opts={"node_local": True})``) exactly.
    """
    from functools import partial

    from repro.kernels.block_jacobi.ref import block_jacobi_apply_ref
    from repro.kernels.trisweep.ref import block_sweep_ref

    n = mesh.shape["nodes"]
    if n != problem.part.n_nodes:
        # the slab restriction, the twin, and the shard_map index shift all
        # assume one partition slab per mesh device; a mismatched mesh would
        # silently clamp cross-shard loads to wrong blocks
        raise ValueError(
            f"node-local sweeps need one partition slab per mesh device: "
            f"mesh has {n} nodes, partition has {problem.part.n_nodes}")
    pc, adopted = _ensure_node_local(problem, n)
    variant = (f"node-local {pc.name} (auto twin)" if adopted
               else f"node-local {pc.name}")
    per = (pc.m // pc.block) // n
    put = lambda a: jax.device_put(a, NamedSharding(mesh, P("nodes")))

    if pc.name == "ssor":
        statics = tuple(map(put, (pc.lo_idx, pc.lo_n, pc.lo_data, pc.up_idx,
                                  pc.up_n, pc.up_data, pc.dinv,
                                  pc.mid_blocks)))

        @partial(shard_map, mesh=mesh, in_specs=(P("nodes"),) * 9,
                 out_specs=P("nodes"), check_vma=False)
        def apply_local(lo_idx, lo_n, lo_data, up_idx, up_n, up_data, dinv,
                        mid, r):
            base = jax.lax.axis_index("nodes") * per     # global -> slab ids
            y = block_sweep_ref(lo_idx - base, lo_n, lo_data, dinv, r,
                                reverse=False)
            w = block_jacobi_apply_ref(mid, y)
            return block_sweep_ref(up_idx - base, up_n, up_data, dinv, w,
                                   reverse=True)
    else:                                                # ic0
        statics = tuple(map(put, (pc.lo_idx, pc.lo_n, pc.lo_data, pc.up_idx,
                                  pc.up_n, pc.up_data, pc.dinv_f,
                                  pc.dinv_b)))

        @partial(shard_map, mesh=mesh, in_specs=(P("nodes"),) * 9,
                 out_specs=P("nodes"), check_vma=False)
        def apply_local(lo_idx, lo_n, lo_data, up_idx, up_n, up_data,
                        dinv_f, dinv_b, r):
            base = jax.lax.axis_index("nodes") * per
            y = block_sweep_ref(lo_idx - base, lo_n, lo_data, dinv_f, r,
                                reverse=False)
            return block_sweep_ref(up_idx - base, up_n, up_data, dinv_b, y,
                                   reverse=True)

    return (lambda r: apply_local(*statics, r)), variant


def _sharded_chebyshev_precond(problem: Problem, mesh: Mesh):
    """Chebyshev apply for the sharded runtime: the polynomial recurrence
    over the all-gather sharded SpMV — no node-local approximation needed
    (the operator is d distributed matvecs, identical algebra to the
    single-device apply)."""
    from repro.kernels.chebyshev.chebyshev import cheb_recurrence

    pc = problem.precond
    mv = sharded_matvec(problem.a, mesh)
    vec = NamedSharding(mesh, P("nodes"))

    def apply_(r):
        z = cheb_recurrence(mv, r, lo=pc.lo, hi=pc.hi, degree=pc.degree)
        return jax.lax.with_sharding_constraint(z, vec)

    return apply_, "spmv-distributed chebyshev"


def _ops_from_parts(backend, mv, precond, dot, variant, constrain):
    """Assemble the (sharded | mesh-mirror) SolverOps bundle from its parts —
    one definition of the update/dot structure, so the two runtimes cannot
    drift apart numerically. Batch-polymorphic: with (B, M) vectors the
    scalars arrive as (B,) and broadcast over the trailing row axis
    (``_expand`` is the identity on the unbatched path)."""
    from repro.core.ops import SolverOps
    from repro.core.pcg import _expand

    def matvec_dot(p):
        q = mv(p)
        return q, dot(p, q)

    def update(alpha, x, r, p, q):
        a = _expand(alpha, x)
        x_new = constrain(x + a * p)
        r_new = constrain(r - a * q)
        z_new = constrain(precond(r_new))
        return x_new, r_new, z_new, dot(r_new, z_new)

    return SolverOps(backend, mv, matvec_dot, precond, update, variant, dot)


@dataclasses.dataclass(frozen=True)
class _MeshOps:
    """``bind`` of the sharded (``mesh`` set) and mesh-mirror (``mesh``
    None) bundles over the operands (data, idx[, pinv]): the Block-ELL
    matrix, and the block-Jacobi inverse blocks unless ``precond`` is
    another preconditioner's apply."""
    mesh: Mesh | None
    n_nodes: int
    m: int
    lane: int
    batch: int = 0
    precond: Callable | None = None
    variant: str = ""

    def __call__(self, operands):
        from repro.core.ops import jacobi_apply
        from repro.kernels.spmv.ref import spmv_seq_ref

        data, idx, *pinv = operands
        batch = self.batch
        if self.precond is not None:
            precond = self.precond
        elif batch:
            precond = lambda r: jnp.stack([jacobi_apply(pinv[0], r[i])
                                           for i in range(batch)])
        else:
            precond = lambda r: jacobi_apply(pinv[0], r)
        if self.mesh is not None:
            spmv = _sharded_spmv(self.mesh, batch)
            vec = NamedSharding(self.mesh,
                                P(None, "nodes") if batch else P("nodes"))
            return _ops_from_parts(
                "sharded", lambda x: spmv(data, idx, x), precond,
                sharded_dot(self.mesh, self.m, self.lane, batch=batch),
                self.variant,
                lambda v: jax.lax.with_sharding_constraint(v, vec))
        mv1 = lambda x: spmv_seq_ref(data, idx, x)
        dot1 = mesh_dot(self.n_nodes, self.m, self.lane)
        if batch:
            return _ops_from_parts(
                "mesh-mirror",
                lambda x: jnp.stack([mv1(x[i]) for i in range(batch)]),
                precond,
                lambda u, v: jnp.stack([dot1(u[i], v[i])
                                        for i in range(batch)]),
                "mesh-mirror", lambda v: v)
        return _ops_from_parts("mesh-mirror", mv1, precond, dot1,
                               f"mesh-mirror {self.variant}".strip(),
                               lambda v: v)


def _mesh_ops(problem: Problem, mesh, n_nodes: int, batch: int = 0,
              precond=None, variant: str = ""):
    from repro.core.ops import bound

    a = problem.a
    operands = ((a.data, a.idx) if precond is not None
                else (a.data, a.idx, problem.pinv_blocks))
    return bound(_MeshOps(mesh, n_nodes, problem.m, problem.part.bn, batch,
                          precond, variant), operands)


def sharded_solver_ops(problem: Problem, mesh: Mesh, batch: int = 0):
    """SolverOps bundle for the distributed runtime.

    The same ESRP/IMCR core from ``repro.core`` runs through this bundle
    unchanged: the SpMV is the all-gather sharded matvec, every vector
    produced by the update is constrained back to the block-row placement
    (so XLA keeps the whole iteration SPMD-partitioned instead of
    replicating intermediates), and the pᵀq / rᵀz dots lower to per-node
    partials + the natural psum across the "nodes" axis. Cached per
    (problem, mesh); the placed matrix and block-Jacobi blocks are the
    bundle's operands.

    Every registered preconditioner is accepted: block-Jacobi keeps the
    seed's einsum over re-placed blocks, SSOR/IC(0) run their node-local
    (additive-Schwarz) sweeps under ``shard_map`` (building and adopting
    the twin when the instance still has cross-slab coupling — see
    ``_sharded_sweep_precond``), and Chebyshev distributes through the
    sharded SpMV. ``SolveReport.precond_variant`` records which variant ran;
    compare iteration counts against the global-sweep reference with
    ``attach_local_delta``. ``mesh_mirror_ops`` builds the single-device
    bundle this one is bit-identical to in f64.

    ``batch`` > 0 builds the batched-axis bundle: all vectors are (B, M)
    with the row axis sharded (P(None, "nodes")), the SpMV gathers every
    member's halo in one collective, the dots reduce to a replicated (B,)
    vector, and the preconditioner applies per member (block-Jacobi only —
    the node-local sweeps and Chebyshev recurrence pend).
    """
    cache = getattr(problem, "_sharded_ops_cache", None)
    # unbatched entries keep the bare-mesh key (pre-batch callers index
    # the cache by mesh); batched bundles get their own keys beside them
    key = mesh if not batch else (mesh, batch)
    if cache is not None and key in cache:
        return cache[key]
    n = mesh.shape["nodes"]
    variant = ""
    precond = None
    name = problem.precond_name
    if batch and name != "jacobi":
        raise NotImplementedError(
            f"batched sharded runtime supports the block-Jacobi "
            f"preconditioner only (got {name!r})")
    if name == "chebyshev":
        precond, variant = _sharded_chebyshev_precond(problem, mesh)
    elif name in ("ssor", "ic0"):
        precond, variant = _sharded_sweep_precond(problem, mesh)
    elif name != "jacobi":
        raise NotImplementedError(
            f"sharded runtime has no distributed apply for "
            f"preconditioner {name!r}")
    ops = _mesh_ops(problem, mesh, n, batch, precond, variant)
    # re-fetch: building the bundle may have *cleared* the cache attribute
    # (twin adoption drops every closure cache, this one included)
    cache = getattr(problem, "_sharded_ops_cache", None)
    if cache is None:
        cache = {}
        problem._sharded_ops_cache = cache
    cache[key] = ops
    return ops


def mesh_mirror_ops(problem: Problem, n_nodes: int, batch: int = 0):
    """Single-device SolverOps with the *mesh's* reduction structure: the
    sequential-k SpMV, per-node partial dots summed over the node axis, and
    the same preconditioner variant the sharded runtime applies (adopting
    the node-local twin exactly like ``_sharded_sweep_precond`` would).

    This is the single-device reference trajectory the sharded runtime
    rejoins **bit-identically in f64** — the distributed analogue of the
    jnp-backend's kernel-mirrored reduction order. Use it as the reference
    for sharded parity/scenario tests; against the plain jnp backend only
    iteration-count equality holds (flat vs per-node dot association).

    ``batch`` > 0 mirrors the batched sharded bundle: every op unrolls the
    unbatched mesh-structured subgraph per member, so the batched sharded
    trajectory rejoins this reference bit-identically per member.
    """
    cache = getattr(problem, "_mesh_mirror_cache", None)
    if cache is None:
        cache = {}
        problem._mesh_mirror_cache = cache
    key = (n_nodes, batch)
    if key not in cache:
        from repro.kernels.spmv.ref import spmv_seq_ref

        if n_nodes != problem.part.n_nodes:
            raise ValueError(
                f"mesh mirror needs one partition slab per simulated node: "
                f"asked n={n_nodes}, partition has {problem.part.n_nodes}")
        variant = ""
        precond = None
        name = problem.precond_name
        if batch and name != "jacobi":
            raise NotImplementedError(
                f"batched mesh mirror supports the block-Jacobi "
                f"preconditioner only (got {name!r})")
        if name == "chebyshev":
            from repro.kernels.chebyshev.chebyshev import cheb_recurrence

            a, pc = problem.a, problem.precond
            matvec = lambda x: spmv_seq_ref(a.data, a.idx, x)
            precond = lambda r: cheb_recurrence(matvec, r, lo=pc.lo,
                                                hi=pc.hi, degree=pc.degree)
            variant = "spmv-distributed chebyshev"
        elif name in ("ssor", "ic0"):
            pc, adopted = _ensure_node_local(problem, n_nodes)
            precond = lambda r: pc.apply(r, backend="jnp")
            variant = (f"node-local {pc.name} (auto twin)" if adopted
                       else f"node-local {pc.name}")
            cache = {}
            problem._mesh_mirror_cache = cache    # adoption dropped the attr
        elif name != "jacobi":
            raise NotImplementedError(name)
        cache[key] = _mesh_ops(problem, None, n_nodes, batch, precond,
                               variant)
    return cache[key]


def attach_local_delta(report, reference) -> None:
    """Record on ``report`` the iteration-count delta of the node-local
    (additive-Schwarz) run vs the global-sweep reference solve — the price
    of making the sweeps partition over the mesh axis. If either run
    stopped at max_iters without converging, ``converged_iter`` is just
    where the budget ran out and the delta would be meaningless — left
    ``None``."""
    if not (report.converged and reference.converged):
        report.local_delta_iters = None
        return
    report.local_delta_iters = report.converged_iter - reference.converged_iter


# --------------------------------------------------------------------------- #
# banded specialization: ppermute halo exchange (the paper's neighbour sends)
# --------------------------------------------------------------------------- #
def ring_halo_matvec(a: BlockEll, part, mesh: Mesh, halo_tiles: int):
    """Banded SpMV with explicit ±1 ring halo exchange.

    Requires every referenced column tile of node s to lie within
    [s's first tile - halo_tiles, s's last tile + halo_tiles] — checked at
    build time against the sparsity structure. ``halo_tiles`` column tiles
    are sent to each ring neighbour per product (the paper's I_{s,s±1});
    communication volume = 2 * halo_tiles * bn * itemsize per node.
    """
    n = part.n_nodes
    cpt = part.col_tiles_per_node
    if n < 2:
        # a 1-node "ring" sends both halos to itself; ppermute with self
        # edges silently yields zeros — reject at build time
        raise ValueError(
            f"ring halo exchange needs >= 2 nodes, got n_nodes={n}")
    if not 1 <= halo_tiles <= cpt:
        # halo_tiles > cpt would make xt[-halo_tiles:] silently slice the
        # whole slab (and halo_tiles = 0 the empty one), failing later with
        # an opaque concatenate shape error instead of here
        raise ValueError(
            f"halo_tiles={halo_tiles} must be within [1, col_tiles_per_node"
            f"={cpt}]: each node can only send tiles it owns")
    # static check: band fits the halo
    idx = np.asarray(a.idx)
    nblk = np.asarray(a.nblk)
    rpt = part.row_tiles_per_node
    for s in range(n):
        rows = slice(s * rpt, (s + 1) * rpt)
        valid = idx[rows][np.arange(a.kmax)[None, :] < nblk[rows][:, None]]
        if valid.size and (valid.min() < s * cpt - halo_tiles
                           or valid.max() >= (s + 1) * cpt + halo_tiles):
            raise ValueError(f"node {s}: sparsity exceeds halo_tiles="
                             f"{halo_tiles}")

    bn = a.bn

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("nodes"), P("nodes"), P("nodes")),
        out_specs=P("nodes"), check_vma=False)
    def mv(data, idx_l, x):
        # x: local slab (rows_per_node,) -> tiles (cpt, bn)
        xt = x.reshape(cpt, bn)
        lo = jax.lax.ppermute(xt[-halo_tiles:], "nodes",
                              [(i, (i + 1) % n) for i in range(n)])
        hi = jax.lax.ppermute(xt[:halo_tiles], "nodes",
                              [(i, (i - 1) % n) for i in range(n)])
        ext = jnp.concatenate([lo, xt, hi], axis=0)   # (cpt + 2*halo, bn)
        me = jax.lax.axis_index("nodes")
        base = me * cpt - halo_tiles
        local_idx = jnp.clip(idx_l - base, 0, ext.shape[0] - 1)
        gathered = ext[local_idx]                     # (rpt, kmax, bn)
        y = jnp.einsum("rkij,rkj->ri", data, gathered)
        return y.reshape(-1)

    return lambda x: mv(a.data, a.idx, x)


# --------------------------------------------------------------------------- #
# physical ASpMV redundancy (paper §2.2.1 on the ICI ring)
# --------------------------------------------------------------------------- #
def _designated_sends(plan, part):
    """Host-side static send lists for the §2.2.1 redundancy pushes: for
    each k in 1..phi, an (n_nodes, width_k) int32 array of the column tiles
    node s ships to its designated destination d_{s,k} (-1 = padding) —
    every tile of s the destination holds after one ASpMV (natural + extra,
    i.e. the queue entry the buddy can serve after a failure) — plus the
    matching ppermute edge list."""
    from repro.sparse.partition import neighbor

    n = part.n_nodes
    send_idx_k, perms = [], []
    for k in range(1, plan.phi + 1):
        rows = []
        for s in range(n):
            d = neighbor(s, k, n)
            lo, hi = part.node_col_tiles(s)
            rows.append([t for t in range(lo, hi) if plan.holders[t, d]
                         and part.owner_of_col_tile(t) == s])
        width = max(len(r) for r in rows)
        idx = np.full((n, width), -1, np.int32)
        for s, r in enumerate(rows):
            idx[s, :len(r)] = r
        send_idx_k.append(idx)
        perms.append([(s, neighbor(s, k, n)) for s in range(n)])
    return send_idx_k, perms


def aspmv_push(plan, part, mesh: Mesh):
    """Materialize the augmented-SpMV redundancy sends as ring ppermutes.

    For each k in 1..phi, every node sends the column tiles of the input
    vector listed in I_{s,d_{s,k}} ∪ R^c_{s,k} to its designated neighbour
    d_{s,k} (Eq. 1) — one ``collective-permute`` per k, payload padded to the
    largest per-node send count (static shape). Returns a function
    ``push(x) -> list over k of (recv_tiles, recv_idx)`` where node d's row
    of ``recv_tiles`` holds the tile values it received (its share of the
    paper's redundancy queue entry) and ``recv_idx`` the *global* column-tile
    ids (-1 = padding). ``redundancy_queue`` is the hot-loop form: the same
    sends scattered straight into the device-resident queue entry.
    """
    from functools import partial

    n = part.n_nodes
    cpt = part.col_tiles_per_node
    bn = part.bn
    send_idx_k, perms = _designated_sends(plan, part)

    def make_one(k):
        idx = jax.device_put(jnp.asarray(send_idx_k[k]),
                             NamedSharding(mesh, P("nodes")))
        perm = perms[k]

        @partial(shard_map, mesh=mesh, in_specs=(P("nodes"), P("nodes")),
                 out_specs=(P("nodes"), P("nodes")), check_vma=False)
        def push(x_local, idx_local):
            xt = x_local.reshape(cpt, bn)
            me = jax.lax.axis_index("nodes")
            local = jnp.clip(idx_local[0] - me * cpt, 0, cpt - 1)
            payload = jnp.where((idx_local[0] >= 0)[:, None], xt[local], 0.0)
            recv = jax.lax.ppermute(payload, "nodes", perm)
            recv_idx = jax.lax.ppermute(idx_local[0], "nodes", perm)
            return recv[None], recv_idx[None]

        return lambda x: push(x, idx)

    fns = [make_one(k) for k in range(plan.phi)]
    return lambda x: [f(x) for f in fns]


def redundancy_queue(plan, part, mesh: Mesh, batch: int = 0):
    """Device-resident ASpMV redundancy queue entry (paper §2.2.1).

    One push physically places, on every node d, a copy of each column tile
    the plan says d holds for another owner: the designated sends travel as
    the same ring ``ppermute``s as ``aspmv_push`` (one hop per k — the
    paper's explicit redundancy traffic), and tiles that already travel
    *naturally* to a non-designated receiver are retained out of the
    all-gather the SpMV performs anyway (the ESR zero-extra-communication
    insight). Returns ``(hold_idx, push)``:

      hold_idx  (n_nodes, width) int32, static: hold_idx[d, j] is the global
                column tile whose copy lives in slot j of node d's queue
                entry (-1 = padding).
      push      x -> (n_nodes, width, bn): node d's row holds the tile
                values it received/retained this push — its physical share
                of the redundancy queue, sharded over the "nodes" axis.

    ``batch`` > 0 pushes all B members' directions in the same collectives:
    x is (B, M) (row axis sharded), the payload of each ppermute is
    (B, width_k, bn), and the entry comes back (B, n_nodes, width, bn) with
    the node axis sharded — per member identical to the unbatched entry
    (every op is data movement; nothing reduces across members).
    """
    from functools import partial

    from repro.sparse.partition import neighbor

    n = part.n_nodes
    cpt = part.col_tiles_per_node
    bn = part.bn
    ct = part.col_tiles
    owner = part.owner_of_col_tile(np.arange(ct))

    hold_rows = [np.nonzero(plan.holders[:, d] & (owner != d))[0]
                 for d in range(n)]
    width = max((r.size for r in hold_rows), default=0)
    if width == 0:
        raise ValueError("redundancy plan holds no off-owner copies — "
                         "nothing to queue (n_nodes < 2?)")
    hold_idx = np.full((n, width), -1, np.int32)
    slot_of = [dict() for _ in range(n)]
    for d, r in enumerate(hold_rows):
        hold_idx[d, :r.size] = r
        slot_of[d].update({int(t): j for j, t in enumerate(r)})

    send_idx_k, perms = _designated_sends(plan, part)
    # per k: the receiving slot of each ppermute lane (node d receives the
    # tiles its k-th *reverse* neighbour sent; the lane order is the
    # sender's, so map sender-lane tile -> receiver hold slot)
    recv_slot_k = []
    for k in range(plan.phi):
        wk = send_idx_k[k].shape[1]
        rs = np.full((n, wk), -1, np.int32)
        for s in range(n):
            d = neighbor(s, k + 1, n)
            for j, t in enumerate(send_idx_k[k][s]):
                if t >= 0:
                    rs[d, j] = slot_of[d][int(t)]
        recv_slot_k.append(rs)
    # natural retention: hold tiles not covered by any designated send
    covered = [set() for _ in range(n)]
    for k in range(plan.phi):
        for s in range(n):
            d = neighbor(s, k + 1, n)
            covered[d].update(int(t) for t in send_idx_k[k][s] if t >= 0)
    nat_rows = [[t for t in hold_rows[d] if int(t) not in covered[d]]
                for d in range(n)]
    wn = max(len(r) for r in nat_rows)
    nat_idx = np.full((n, max(wn, 1)), -1, np.int32)
    nat_slot = np.full((n, max(wn, 1)), -1, np.int32)
    for d, r in enumerate(nat_rows):
        for j, t in enumerate(r):
            nat_idx[d, j] = t
            nat_slot[d, j] = slot_of[d][int(t)]

    put = lambda a: jax.device_put(jnp.asarray(a),
                                   NamedSharding(mesh, P("nodes")))
    statics = ([put(i) for i in send_idx_k] + [put(r) for r in recv_slot_k]
               + [put(nat_idx), put(nat_slot)])
    phi = plan.phi

    if batch:
        out_sh = NamedSharding(mesh, P(None, "nodes"))

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(None, "nodes"),) + (P("nodes"),) * len(statics),
                 out_specs=P(None, "nodes"), check_vma=False)
        def push_b(x_local, *stat):
            send = stat[:phi]
            rslot = stat[phi:2 * phi]
            nidx, nslot = stat[2 * phi], stat[2 * phi + 1]
            xt = x_local.reshape(batch, cpt, bn)
            me = jax.lax.axis_index("nodes")
            buf = jnp.zeros((batch, width + 1, bn), x_local.dtype)
            for k in range(phi):
                sidx = send[k][0]
                local = jnp.clip(sidx - me * cpt, 0, cpt - 1)
                payload = jnp.where((sidx >= 0)[None, :, None],
                                    xt[:, local], 0.0)
                recv = jax.lax.ppermute(payload, "nodes", perms[k])
                slot = rslot[k][0]
                buf = buf.at[:, jnp.where(slot >= 0, slot, width)].set(recv)
            if wn:
                xg = jax.lax.all_gather(xt, "nodes", axis=1, tiled=True)
                ni, ns = nidx[0], nslot[0]
                vals = xg[:, jnp.clip(ni, 0, ct - 1)]
                buf = buf.at[:, jnp.where(ns >= 0, ns, width)].set(vals)
            return buf[:, None, :width]

        fn_b = sync_free(lambda x: jax.lax.with_sharding_constraint(
            push_b(x, *statics), out_sh))
        return hold_idx, fn_b

    out_sh = NamedSharding(mesh, P("nodes"))

    @partial(shard_map, mesh=mesh,
             in_specs=(P("nodes"),) * (1 + len(statics)),
             out_specs=P("nodes"), check_vma=False)
    def push(x_local, *stat):
        send = stat[:phi]
        rslot = stat[phi:2 * phi]
        nidx, nslot = stat[2 * phi], stat[2 * phi + 1]
        xt = x_local.reshape(cpt, bn)
        me = jax.lax.axis_index("nodes")
        # scratch row `width` swallows the padding-lane writes, so a pad can
        # never overwrite (or 0.0-perturb) a real slot
        buf = jnp.zeros((width + 1, bn), x_local.dtype)
        for k in range(phi):
            sidx = send[k][0]
            local = jnp.clip(sidx - me * cpt, 0, cpt - 1)
            payload = jnp.where((sidx >= 0)[:, None], xt[local], 0.0)
            recv = jax.lax.ppermute(payload, "nodes", perms[k])
            slot = rslot[k][0]
            buf = buf.at[jnp.where(slot >= 0, slot, width)].set(recv)
        if wn:
            xg = jax.lax.all_gather(xt, "nodes", tiled=True)   # (ct, bn)
            ni, ns = nidx[0], nslot[0]
            vals = xg[jnp.clip(ni, 0, ct - 1)]
            buf = buf.at[jnp.where(ns >= 0, ns, width)].set(vals)
        return buf[None, :width]

    # the push runs inside sync-free chunk bodies: collectives only, no
    # host round-trip (registered with the repro.analysis host-sync pass)
    fn = sync_free(lambda x: jax.lax.with_sharding_constraint(
        push(x, *statics), out_sh))
    return hold_idx, fn


def _node_axis_zeroer(mesh: Mesh, axis: int):
    """shard_map op zeroing entire shards of the devices flagged in ``dead``
    — the physical failure injection (no gather/replicate round-trip; each
    device tests only its own axis index). ``axis`` is the array axis the
    "nodes" mesh axis shards."""
    spec = P(*([None] * axis + ["nodes"]))

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, P()),
                       out_specs=spec, check_vma=False)
    def zero(v, dead):
        me = jax.lax.axis_index("nodes")
        return jnp.where(dead[me], jnp.zeros_like(v), v)

    return zero


class ShardedFailureRuntime:
    """Device-resident failure semantics for ``solve_resilient`` on the mesh.

    Plugs the three physical pieces into the driver:

      * ``init_queue`` / ``queue_push`` — the ``ESRPState.rq`` redundancy
        queue: per-device copies physically placed on the designated
        neighbours at every storage push (``redundancy_queue``).
      * ``lose_esrp`` / ``lose_pcg`` — failure injection as a ``shard_map``
        zeroing of the failed devices' shards only: live vectors, starred
        locals, the device's own queue rows AND the copies it held for
        others (a failed node loses everything node-resident, paper §4).
      * ``assemble_pair`` — reconstruction inputs: p^(j-1), p^(j) restricted
        to the failed rows are read from *surviving devices'* ``rq`` shards
        (host-side static source choice via ``RedundancyPlan.copy_sources``,
        stricter than the static plan: copies wiped by an earlier event are
        stale until the next storage push refreshes them).

    Also accounts the per-preconditioner static-state reload a replacement
    node performs (``precond.local.static_reload_bytes``) —
    ``EventReport.precond_reload_bytes``.
    """

    def __init__(self, problem: Problem, mesh: Mesh, batch: int = 0):
        n = mesh.shape["nodes"]
        if n != problem.part.n_nodes:
            raise ValueError(
                f"failure runtime needs one partition slab per mesh device: "
                f"mesh has {n} nodes, partition has {problem.part.n_nodes}")
        self.problem = problem
        self.mesh = mesh
        self.n = n
        self.part = problem.part
        self.batch = batch  # > 0: the runtime serves the batched (B, M)
        #                     solve — queue entries and injections carry the
        #                     member axis; one event strikes all B members
        self.plan = None
        self.queue_push = None
        self._hold_idx = None
        self._slot_of = None
        self._queues = {}   # phi -> (hold_idx, push, slot_of): the push
        #                     closure must keep a stable identity across
        #                     solves (the jitted chunk runners key their
        #                     compile cache on it)
        self._zeroers = {}  # sharded-node-axis index -> shard_map zeroer
        #                     (vectors/queues of both the unbatched and
        #                     batched layouts resolve their axis by ndim)
        self._wiped: dict[int, int] = {}   # device -> newest q tag when its
        #                                    held copies were zeroed
        self.last_sources: tuple[int, ...] = ()

    def _zero(self, v, dead, axis: int):
        z = self._zeroers.get(axis)
        if z is None:
            z = self._zeroers[axis] = _node_axis_zeroer(self.mesh, axis)
        return z(v, dead)

    # -- driver hooks ------------------------------------------------------ #
    def bind_plan(self, plan) -> None:
        """Called by the driver once the RedundancyPlan exists: build (or
        reuse — the driver builds a fresh plan object per solve, but the
        layout only depends on φ) the physical queue layout + push closure,
        and reset the wiped-copy tracking for the new run."""
        self.plan = plan
        self._wiped.clear()
        entry = self._queues.get(plan.phi)
        if entry is None:
            hold_idx, push = redundancy_queue(plan, self.part, self.mesh,
                                              batch=self.batch)
            slot_of = [{int(t): j for j, t in enumerate(row) if t >= 0}
                       for row in hold_idx]
            entry = self._queues[plan.phi] = (hold_idx, push, slot_of)
        self._hold_idx, self.queue_push, self._slot_of = entry

    def init_queue(self, st, reset: bool = False):
        """Attach the empty (3, n, width, bn) device-resident queue to a
        fresh ESRPState (placed on the node axis; (3, B, n, width, bn) on
        the batched runtime). reset=True also forgets wiped-copy tracking
        (a restart rebuilds everything from scratch)."""
        if reset:
            self._wiped.clear()
        w = self._hold_idx.shape[1]
        if self.batch:
            rq = jax.device_put(
                jnp.zeros((3, self.batch, self.n, w, self.part.bn),
                          self.problem.b.dtype),
                NamedSharding(self.mesh, P(None, None, "nodes")))
        else:
            rq = jax.device_put(
                jnp.zeros((3, self.n, w, self.part.bn),
                          self.problem.b.dtype),
                NamedSharding(self.mesh, P(None, "nodes")))
        st = st._replace(rq=rq)
        if not isinstance(st.q_sums, tuple):
            # per-holder checksums of the physical copies ride along with the
            # host-visible q checksums (same push-time write protocol);
            # batched entries checksum per member: (3, B, n)
            if self.batch:
                st = st._replace(rq_sums=jax.device_put(
                    jnp.zeros((3, self.batch, self.n),
                              self.problem.b.dtype),
                    NamedSharding(self.mesh, P(None, None, "nodes"))))
            else:
                st = st._replace(rq_sums=jax.device_put(
                    jnp.zeros((3, self.n), self.problem.b.dtype),
                    NamedSharding(self.mesh, P(None, "nodes"))))
        return st

    def _dead(self, failed) -> jnp.ndarray:
        dead = np.zeros(self.n, bool)
        dead[list(failed)] = True
        return jnp.asarray(dead)

    def lose_pcg(self, pcg, failed):
        """Zero the failed devices' shards of the live vectors (x, r, z, p).
        The sharded node axis is resolved by rank — (M,) and batched (B, M)
        vectors both shard their last axis — so one injection covers both
        layouts (a fail-stop event wipes a device's rows for all B members
        at once)."""
        dead = self._dead(failed)
        l = lambda v: self._zero(v, dead, v.ndim - 1)
        return pcg._replace(x=l(pcg.x), r=l(pcg.r), z=l(pcg.z), p=l(pcg.p))

    def lose_esrp(self, st, failed):
        """Full §4 injection for an ESRPState: live vectors, starred locals,
        the failed devices' own queue rows, and the redundancy copies they
        held for others (their ``rq`` rows)."""
        dead = self._dead(failed)
        l = lambda v: self._zero(v, dead, v.ndim - 1)
        st = st._replace(
            pcg=self.lose_pcg(st.pcg, failed),
            x_s=l(st.x_s), r_s=l(st.r_s), z_s=l(st.z_s), p_s=l(st.p_s),
            q=self._zero(st.q, dead, st.q.ndim - 1))
        if not isinstance(st.rq, tuple):
            # (3, n, w, bn) or batched (3, B, n, w, bn): holder axis is
            # always three from the end
            st = st._replace(rq=self._zero(st.rq, dead, st.rq.ndim - 3))
        # keep checksums consistent with the zeroed copies (sum of zeros = 0)
        # so the wipe itself never reads as queue corruption; the dead-holder
        # column broadcasts over every leading axis ((3, n), (3, B, n), and
        # per-slab (3, ..., n_slabs) layouts alike — the latter only when the
        # slab count equals the node count, hence the shape guard)
        def _wipe_col(sums):
            col = jnp.asarray(self._dead(failed)).reshape(
                (1,) * (sums.ndim - 1) + (-1,))
            return jnp.where(col, 0, sums)
        if not isinstance(st.q_sums, tuple) \
                and st.q_sums.shape[-1] == self.n:
            st = st._replace(q_sums=_wipe_col(st.q_sums))
        if not isinstance(st.rq_sums, tuple):
            st = st._replace(rq_sums=_wipe_col(st.rq_sums))
        return st

    def lose_live(self, st, failed):
        """SDC-repair injection: discard the flagged devices' live vectors
        and starred locals but keep their queue rows and held copies —
        nothing was physically lost, the stored redundancy is still intact
        (and checksum-verified at read time)."""
        dead = self._dead(failed)
        l = lambda v: self._zero(v, dead, v.ndim - 1)
        return st._replace(pcg=self.lose_pcg(st.pcg, failed),
                           x_s=l(st.x_s), r_s=l(st.r_s), z_s=l(st.z_s),
                           p_s=l(st.p_s))

    def mark_wiped(self, failed, newest_tag: int) -> None:
        """Record that the failed devices' held copies are gone: every queue
        entry tagged <= ``newest_tag`` has their rows zeroed. Only entries
        pushed *later* (a strictly newer tag) carry fresh copies again."""
        for d in failed:
            self._wiped[int(d)] = int(newest_tag)

    def _checksum_valid(self, st, slots) -> np.ndarray:
        """Read-time verification of the device-resident copies: recompute
        each holder's checksum for the slots about to be read and exclude
        holders whose stored copy no longer matches its push-time checksum
        (a corrupted copy must never enter Alg. 2 — ``copy_sources`` falls
        back to an alternate holder, or raises when none is left). The
        comparison is tolerance-based (differing jit contexts may reduce in
        a different order) and NaN-unsafe values compare as corrupt."""
        if isinstance(getattr(st, "rq_sums", ()), tuple):
            return np.ones(self.n, bool)
        ok = np.ones(self.n, bool)
        for slot in sorted({int(s) for s in slots}):
            # (n, w, bn) or batched (B, n, w, bn): reduce the tile axes,
            # leaving per-holder (or per-member-per-holder) sums
            actual = np.asarray(
                jax.device_get(st.rq[slot]).sum(axis=(-2, -1)))
            ref = np.asarray(jax.device_get(st.rq_sums[slot]))
            good = np.abs(actual - ref) <= 1e-9 * (np.abs(ref) + 1.0)
            if good.ndim == 2:
                # a holder is usable only if EVERY member's copy verifies —
                # Alg. 2 assembles the whole batch from one source choice
                good = good.all(axis=0)
            ok &= good
        return ok

    def _valid_sources(self, read_tag: int) -> np.ndarray:
        """Which devices hold fresh copies in a queue entry tagged
        ``read_tag``. Must be the tag of the *oldest slot actually read* —
        validating against the newest tag would declare a device fresh as
        soon as any later push landed, even though recovery falls back to a
        pre-refresh slot pair whose rows are still zero (e.g. a second
        failure striking exactly on a stage's first push)."""
        return np.array([d not in self._wiped
                         or read_tag > self._wiped[d]
                         for d in range(self.n)])

    def assemble_pair(self, st, prev_slot: int, curr_slot: int, failed):
        """Rebuild full-length p^(j-1), p^(j): surviving rows from each
        node's own queue history (``st.q`` — failed rows were zeroed by the
        injection), failed rows gathered from the surviving devices'
        device-resident ``rq`` shards. Returns (p_prev, p_curr, sources)."""
        from repro.core import failures

        oldest_read = int(st.q_tags[prev_slot])
        valid = self._valid_sources(oldest_read)
        valid &= self._checksum_valid(st, (prev_slot, curr_slot))
        tiles, src = self.plan.copy_sources(failed, valid)
        slots = np.array([self._slot_of[int(d)][int(t)]
                          for t, d in zip(tiles, src)], np.int32)
        f_rows = jnp.asarray(failures.failed_rows(self.part, list(failed)))
        src_j = jnp.asarray(src.astype(np.int32))
        slots_j = jnp.asarray(slots)

        def fill(slot):
            if self.batch:
                # (3, B, n, w, bn): gather the same (holder, slot) pairs for
                # every member — one Alg. 2 assembly serves the whole batch
                vals = st.rq[slot][:, src_j, slots_j]    # (B, n_tiles, bn)
                return st.q[slot].at[:, f_rows].set(
                    vals.reshape(self.batch, -1))
            vals = st.rq[slot][src_j, slots_j]           # (n_tiles, bn)
            return st.q[slot].at[f_rows].set(vals.reshape(-1))

        self.last_sources = tuple(sorted({int(d) for d in src}))
        return fill(prev_slot), fill(curr_slot), self.last_sources

    def precond_reload(self, failed):
        """Per-preconditioner-state survival check + safe-storage reload
        accounting for the replacement nodes (SSOR/IC(0) slab strips rebuild
        from the COO; Chebyshev bounds are replicated scalars; block-Jacobi
        reloads its inverted diagonal blocks)."""
        from repro.precond.local import static_reload_bytes

        return static_reload_bytes(self.problem, failed)
