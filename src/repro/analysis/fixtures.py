"""Deliberately-broken entry points (``broken.*``) — the analyzer's own
test fixtures. Each violates exactly the invariant its pass checks, so the
test suite can prove every pass actually fires:

  broken.identity     obs=off path that silently gained an extra op
  broken.gating       a "gate" whose disabled branch still runs a dot
  broken.host_sync    a debug callback inside a sync-free chunk body
  broken.determinism  a bare full-vector dot on a bit-identical path
  broken.batch        a reduction across the member axis of a batched op
  broken.sharding     replicated output + member-axis sharding (1-dev mesh)

Excluded from ``--entry all`` (and the CI gate); reachable by explicit
name for tests and demos.
"""
from __future__ import annotations

from repro.analysis.passes import EntrySpec
from repro.analysis.registry import register

_B = 3          # member count of the broken batched entry


@register("broken.identity", broken=True,
          summary="obs=off path with a smuggled extra op")
def _broken_identity():
    import jax
    import jax.numpy as jnp

    def runner(x):
        return jnp.cumsum(x * 2.0 + 1.0)

    def reference(x):
        return jnp.cumsum(x * 2.0)       # the op the runner smuggled in

    x = jnp.ones(32)
    return EntrySpec(
        name="broken.identity", jaxpr=jax.make_jaxpr(runner)(x),
        identity_ref=jax.make_jaxpr(reference)(x),
        identity_label="runner must add zero ops over the reference",
        tags=frozenset())


@register("broken.gating", broken=True,
          summary="cond gate whose disabled branch still pays a dot")
def _broken_gating():
    import jax
    import jax.numpy as jnp

    def runner(x, flag):
        # the "disabled" branch was supposed to be a passthrough but
        # recomputes a (cheaper) dot anyway — the gate saves nothing
        return jax.lax.cond(flag,
                            lambda v: v * (v @ v),
                            lambda v: v * (v[:8] @ v[:8]), x)

    x = jnp.ones(64)
    return EntrySpec(name="broken.gating",
                     jaxpr=jax.make_jaxpr(runner)(x, True),
                     tags=frozenset({"gated"}), min_gates=1)


@register("broken.host_sync", broken=True,
          summary="debug callback inside a sync-free chunk body")
def _broken_host_sync():
    import jax
    import jax.numpy as jnp

    def runner(x):
        def body(c, _):
            jax.debug.print("rnorm={r}", r=jnp.linalg.norm(c))
            return c * 0.5, jnp.linalg.norm(c)

        return jax.lax.scan(body, x, None, length=4)

    return EntrySpec(name="broken.host_sync",
                     jaxpr=jax.make_jaxpr(runner)(jnp.ones(32)),
                     tags=frozenset({"sync_free"}))


@register("broken.determinism", broken=True,
          summary="bare full-vector dot on a bit-identical path")
def _broken_determinism():
    import jax
    import jax.numpy as jnp

    def runner(u, v):
        # no per-block partials, no optimization_barrier: XLA picks the
        # association — a different backend/topology forks the trajectory
        return u @ v + jnp.sum(u * v * 2.0)

    x = jnp.ones(128)
    return EntrySpec(name="broken.determinism",
                     jaxpr=jax.make_jaxpr(runner)(x, x),
                     tags=frozenset({"bit_identical"}))


@register("broken.batch", broken=True,
          summary="reduction across the member axis of a batched op")
def _broken_batch():
    import jax
    import jax.numpy as jnp

    def runner(x):                      # x: (B, M)
        return jnp.sum(x, axis=0) / x.shape[0]   # mixes members!

    return EntrySpec(name="broken.batch",
                     jaxpr=jax.make_jaxpr(runner)(jnp.ones((_B, 64))),
                     tags=frozenset({"batched"}), batch=_B)


@register("broken.sharding", broken=True,
          summary="replicated big output + member-axis sharding")
def _broken_sharding():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("nodes",), axis_types=(AxisType.Auto,))

    def runner(x, xb):                  # x: (M,), xb: (B, M)
        # out_specs P() replicates the whole vector on every device;
        # the batched operand shards the *member* axis across nodes
        rep = shard_map(lambda v: jax.lax.all_gather(v, "nodes",
                                                     tiled=True),
                        mesh=mesh, in_specs=(P("nodes"),), out_specs=P(),
                        check_vma=False)(x)
        mixed = shard_map(lambda v: v * 2.0, mesh=mesh,
                          in_specs=(P("nodes"),), out_specs=P("nodes"),
                          check_vma=False)(xb)
        return rep, mixed

    jaxpr = jax.make_jaxpr(runner)(jnp.ones(512), jnp.ones((_B, 64)))
    return EntrySpec(name="broken.sharding", jaxpr=jaxpr,
                     tags=frozenset({"sharded", "batched"}), batch=_B,
                     mesh_axes=("nodes",), allowed_gathers=0,
                     nodes_axis_by_rank={1: (0,), 2: (1,)})
