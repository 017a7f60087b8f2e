"""ESRP fault tolerance for LM training — the paper's technique as a
first-class framework feature (DESIGN.md §4).

Mapping of the paper's concepts onto the (params, Adam moments) train state
sharded FSDP-style along the ``data`` mesh axis:

  ASpMV piggyback        -> params are all-gathered along the FSDP axis every
                            step anyway; at a storage stage each rank simply
                            *retains* its phi ring-neighbours' param shards
                            from the gather it already performed. Zero extra
                            communication — redundancy inherent to the
                            algorithm, exactly the ESR insight.
  explicit moment push   -> Adam m/v are never communicated by training, so
                            they get a real buddy push (collective-permute
                            ring hops) every T steps — the analogue of the
                            paper's queue/starred storage. Optionally pushed
                            in bf16 ("compressed redundancy", beyond-paper).
  queue-of-2 stages      -> pushes alternate between two buffer slots so a
                            failure *during* a push still finds a complete,
                            consistent (step, params, m, v) set — the
                            training analogue of the paper's queue-of-3
                            rationale (one in-flight + one committed).
  rollback + replay      -> the data pipeline is (seed, step)-deterministic,
                            so recovery rolls everyone to the last storage
                            stage and replays <= T steps, reproducing the
                            undisturbed trajectory bit-for-bit (tested).
  IMCR baseline          -> mode="imcr": params are *pushed* too (no
                            piggyback) — the paper's comparison carried over.

A "node" is a position along the FSDP axis; a node failure loses every shard
slice owned by that position (params, moments — and, like the paper's
replicated scalars, the step counter survives on any rank).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.ft.buddy import BuddyPlan
from repro.obs.trace import span
from repro.train.optimizer import OptState


@dataclasses.dataclass(frozen=True)
class FTConfig:
    mode: str = "esrp"            # "esrp" | "imcr" | "none"
    T: int = 20                   # storage interval (steps)
    phi: int = 1                  # tolerated simultaneous node failures
    n_ranks: int = 8              # FSDP-axis length ("nodes")
    compress: bool = False        # bf16 moment redundancy (beyond-paper)


class FTBuffers(NamedTuple):
    """Redundant storage. Two slots alternate (in-flight safety).

    Per slot: ``local`` is each rank's own snapshot (the paper's starred
    duplicates — zero communication; survivors roll back from it) and the
    ``*_buddies`` lists hold the phi ring-rolled copies (what buddies
    received — replacements rebuild failed shards from them). Both live in
    node memory, so a failure loses their failed-rank slices as well."""
    slot_local: list             # per slot: (params, mu, nu) snapshot trees
    slot_params: list            # per slot: list over k of rolled param trees
    slot_mu: list
    slot_nu: list
    slot_step: list              # step each slot snapshots (-1 = empty)
    active: int                  # slot last written


class ESRPTrainer:
    """Wraps a pjit-able train_step with ESRP storage/recovery."""

    def __init__(self, model, train_step: Callable, pipeline, ft: FTConfig,
                 param_specs=None, obs=None):
        self.model = model
        self.train_step = jax.jit(train_step)
        self.pipeline = pipeline
        self.ft = ft
        self.param_specs = param_specs
        self._plan: Optional[BuddyPlan] = None
        self.push_bytes = 0
        self.push_count = 0
        self.obs = obs                # obs.Tracer: storage/failure/recovery
        #                               spans + per-step loss counter

    # ------------------------------------------------------------------ #
    def init_buffers(self, params, opt: OptState) -> FTBuffers:
        self._plan = BuddyPlan.build(params, self.param_specs,
                                     self.ft.n_ranks, self.ft.phi)
        self._mplan = BuddyPlan.build(opt.mu, None, self.ft.n_ranks,
                                      self.ft.phi)
        empty = [None, None]
        return FTBuffers(slot_local=list(empty), slot_params=list(empty),
                         slot_mu=list(empty), slot_nu=list(empty),
                         slot_step=[-1, -1], active=0)

    def storage_stage(self, params, opt: OptState, bufs: FTBuffers,
                      step: int) -> FTBuffers:
        """Every T steps: retain params (esrp: free at gather time; imcr:
        explicit push) + push moments to phi buddies + local snapshots (the
        paper's starred duplicates, no communication)."""
        if self.ft.mode == "none":
            return bufs
        with span("ft_storage_push", "storage", self.obs, step=step,
                  mode=self.ft.mode) as push_sp:
            dtype = jnp.bfloat16 if self.ft.compress else None
            p_copies = self._plan.push(params)     # esrp: retained, not sent
            mu_copies = self._mplan.push(opt.mu, dtype)
            nu_copies = self._mplan.push(opt.nu, dtype)
            local = (jax.tree.map(jnp.copy, params),
                     jax.tree.map(jnp.copy, opt.mu),
                     jax.tree.map(jnp.copy, opt.nu))
            slot = 1 - bufs.active                 # write the non-active slot
            sl = list(bufs.slot_local)
            sp = list(bufs.slot_params)
            sm = list(bufs.slot_mu)
            sn = list(bufs.slot_nu)
            ss = list(bufs.slot_step)
            sl[slot], sp[slot], sm[slot], sn[slot], ss[slot] = (
                local, p_copies, mu_copies, nu_copies, step)
            # communication accounting: moments always travel; params only
            # under imcr (esrp retains them from the existing FSDP all-gather)
            scale = 0.5 if self.ft.compress else 1.0   # bf16 moment push
            pushed = int(self._mplan.bytes_per_push(opt.mu) * 2 * scale)
            if self.ft.mode == "imcr":
                pushed += self._plan.bytes_per_push(params)
            self.push_bytes += pushed
            self.push_count += 1
            push_sp.args["bytes"] = pushed
            if self.obs is not None:
                self.obs.add_counter("ft_push_bytes", pushed, step=step)
        return FTBuffers(sl, sp, sm, sn, ss, active=slot)

    # ------------------------------------------------------------------ #
    def inject_failure(self, params, opt: OptState, bufs: FTBuffers,
                       failed: list[int]):
        """Zero the failed ranks' shards of ALL node-resident state — live
        params/moments AND the redundancy buffers they host (paper §4: a
        failed node loses everything, including copies it held for others)."""
        lose_p = lambda t: self._plan.lose(t, failed)
        lose_m = lambda t: self._mplan.lose(t, failed)
        params = lose_p(params)
        opt = OptState(mu=lose_m(opt.mu), nu=lose_m(opt.nu), step=opt.step)
        sl, sp, sm, sn = (list(bufs.slot_local), list(bufs.slot_params),
                          list(bufs.slot_mu), list(bufs.slot_nu))
        for i in range(2):
            if bufs.slot_step[i] < 0:
                continue
            sl[i] = (lose_p(sl[i][0]), lose_m(sl[i][1]), lose_m(sl[i][2]))
            sp[i] = [lose_p(t) for t in sp[i]]
            sm[i] = [lose_m(t) for t in sm[i]]
            sn[i] = [lose_m(t) for t in sn[i]]
        bufs = FTBuffers(sl, sp, sm, sn, list(bufs.slot_step), bufs.active)
        return params, opt, bufs

    def recover(self, bufs: FTBuffers, failed: list[int]):
        """Roll everyone back to the last storage stage: survivors restore
        from their local snapshots, failed shards are rebuilt from the
        surviving buddies' rolled copies. Returns (params, opt, step)."""
        slot = bufs.active
        if bufs.slot_step[slot] < 0:
            slot = 1 - slot
        if bufs.slot_step[slot] < 0:
            raise RuntimeError("failure before the first storage stage")
        base_p, base_mu, base_nu = bufs.slot_local[slot]
        params = self._plan.recover(base_p, bufs.slot_params[slot], failed)
        mu = self._mplan.recover(base_mu, bufs.slot_mu[slot], failed)
        nu = self._mplan.recover(base_nu, bufs.slot_nu[slot], failed)
        restart = bufs.slot_step[slot]
        opt = OptState(mu=mu, nu=nu, step=jnp.asarray(restart, jnp.int32))
        return params, opt, restart

    # ------------------------------------------------------------------ #
    def fit(self, params, opt: OptState, n_steps: int,
            scenario: Optional[list] = None,
            fail_at: Optional[int] = None,
            failed_ranks: Optional[list[int]] = None, start_step: int = 0):
        """Training loop with storage stages + an optional *failure
        scenario*: a list of ``FailureEvent(step, ranks)`` entries with the
        solver driver's semantics (``core.failures.normalize_scenario`` —
        simultaneous multi-rank events, staggered multi-event runs, strictly
        increasing step numbers, each event firing exactly once). Recovery
        rolls everyone back to the last storage stage and replays, so a
        later event's step is reached again on the replay *after* its
        predecessor was consumed — rollback never re-arms an event. The
        legacy ``fail_at``/``failed_ranks`` shorthand maps to one event.
        Returns (params, opt, losses: dict step -> loss)."""
        from repro.core.failures import normalize_scenario

        pending = normalize_scenario(scenario, fail_at, failed_ranks,
                                     self.ft.n_ranks)
        bufs = self.init_buffers(params, opt)
        losses = {}
        step = start_step
        while step < n_steps:
            if self.ft.mode != "none" and step % self.ft.T == 0 and step > 0:
                bufs = self.storage_stage(params, opt, bufs, step)
            if pending and step == pending[0].iter:
                ev = pending.pop(0)
                failed = list(ev.nodes)
                with span("ft_inject", "event", self.obs, step=step,
                          ranks=failed):
                    params, opt, bufs = self.inject_failure(params, opt,
                                                            bufs, failed)
                with span("ft_recover", "recovery", self.obs,
                          ranks=failed) as rec_sp:
                    params, opt, step = self.recover(bufs, failed)
                    rec_sp.args["restart_step"] = step
                continue
            batch = self.pipeline.batch_at(step)
            params, opt, metrics = self.train_step(params, opt, batch)
            losses[step] = float(metrics["loss"])
            if self.obs is not None:
                self.obs.counter("ft_step", step=step, loss=losses[step])
            step += 1
        return params, opt, losses

    def run(self, params, opt: OptState, n_steps: int,
            fail_at: Optional[int] = None,
            failed_ranks: Optional[list[int]] = None, start_step: int = 0):
        """Legacy single-event entry point; ``fit`` is the scenario form."""
        return self.fit(params, opt, n_steps, fail_at=fail_at,
                        failed_ranks=failed_ranks, start_step=start_step)
