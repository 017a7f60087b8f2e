"""Solver telemetry subsystem (repro.obs + driver threading — ISSUE 7).

The contract under test:
  * obs=off is FREE: ``run_chunk(..., metrics=False)`` compiles to exactly
    the jaxpr of the pre-telemetry chunk runner (structural alpha-equivalent
    identity via repro.analysis against an inline re-derivation for both
    esrp and imcr), and the driver's default path stays deterministic with
    obs=on rejoining at the same iteration;
  * the on-device metrics ring tells the truth: the per-iteration history
    read back through the chunk record matches a host-side replay (||r||,
    rz bit-tight; push/star flags exactly the Alg. 3 schedule; orth at the
    invariant-noise floor);
  * the span tree is well-formed: every recovery phase nests under its
    fail-stop event span, byte counters are populated from the tier cost
    model, and the exported Chrome-trace passes the validator + file
    round-trips;
  * the program's spans are in a ``jax.profiler`` trace with no Tracer
    attached, nested on one host thread around the device work, and the
    report counters read from them agree with the Tracer's spans;
  * SolveReport/EventReport.to_json is a JSON-safe, schema-versioned dict
    (no device arrays, no NaN) — the BENCH writers' serialization path.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import assert_structurally_equal
from repro.core import esrp, imcr
from repro.core.driver import REPORT_SCHEMA_VERSION, solve_resilient
from repro.core.failures import FailureEvent
from repro.obs import (Tracer, chrome_trace, metrics_snapshot, span,
                       span_tree, validate_chrome_trace, walk_spans,
                       write_chrome_trace, write_jsonl)
from repro.sparse.matrices import build_problem


@pytest.fixture(scope="module")
def problem():
    return build_problem("poisson2d", n_nodes=4, nx=24, ny=24)


@pytest.fixture(scope="module")
def traced():
    """The acceptance scenario: staggered phi=2 ESRP run with the tracer on
    (a simultaneous two-node event, recovery, then a single-node event)."""
    p = build_problem("poisson2d", n_nodes=8, nx=32, ny=32)
    rep = solve_resilient(
        p, strategy="esrp", T=10, phi=2, rtol=1e-8,
        scenario=[FailureEvent(iter=28, nodes=(1, 3)),
                  FailureEvent(iter=38, nodes=(5,))],
        obs=True)
    assert rep.converged and rep.trace is not None
    return rep


# --------------------------------------------------------------------------- #
# obs=off adds ZERO ops (jaxpr identity vs the pre-telemetry runner)
# --------------------------------------------------------------------------- #
def test_esrp_chunk_metrics_off_jaxpr_identity(problem):
    ops = problem.solver_ops("jnp")
    b = problem.b
    st = esrp.esrp_init(ops.matvec, ops.precond, b, dot=ops.dot)
    thresh = jnp.asarray(1e-8, b.dtype)

    def ref_chunk(s0):
        # the pre-telemetry chunk runner, re-derived inline: plain freeze
        # scan with no aux branch anywhere
        def step(s):
            s2 = esrp.esrp_step(s, ops, 10, b=b, rr_every=0, gated=True,
                                push=None)
            return s2, jnp.linalg.norm(s2.pcg.r)

        def body(carry, _):
            s, rnorm = carry
            s, rnorm = jax.lax.cond(
                rnorm < thresh, lambda s_: (s_, rnorm), step, s)
            return (s, rnorm), rnorm

        (s0, _), norms = jax.lax.scan(body, (s0, jnp.linalg.norm(s0.pcg.r)),
                                      None, length=8)
        return s0, norms

    got = jax.make_jaxpr(lambda s: esrp.run_chunk.__wrapped__(
        s, ops, 10, 8, thresh, 0, True, b, None, False))(st)
    want = jax.make_jaxpr(ref_chunk)(st)
    # structural (alpha-equivalent) identity: same strictness as string
    # equality, but a failure reports the first diverging equation instead
    # of two multi-thousand-line reprs
    assert_structurally_equal(got, want, "esrp obs=off adds zero ops")


def test_imcr_chunk_metrics_off_jaxpr_identity(problem):
    ops = problem.solver_ops("jnp")
    b = problem.b
    st = imcr.imcr_init(ops.matvec, ops.precond, b, dot=ops.dot)
    thresh = jnp.asarray(1e-8, b.dtype)
    rows = problem.part.rows_per_node

    def ref_chunk(s0):
        def step(s):
            s2 = imcr.imcr_step(s, ops, 10, 1, rows, True)
            return s2, jnp.linalg.norm(s2.pcg.r)

        def body(carry, _):
            s, rnorm = carry
            s, rnorm = jax.lax.cond(
                rnorm < thresh, lambda s_: (s_, rnorm), step, s)
            return (s, rnorm), rnorm

        (s0, _), norms = jax.lax.scan(body, (s0, jnp.linalg.norm(s0.pcg.r)),
                                      None, length=8)
        return s0, norms

    got = jax.make_jaxpr(lambda s: imcr.run_chunk.__wrapped__(
        s, ops, 10, 1, rows, 8, thresh, True, False))(st)
    want = jax.make_jaxpr(ref_chunk)(st)
    assert_structurally_equal(got, want, "imcr obs=off adds zero ops")


def test_obs_off_deterministic_and_obs_on_rejoins(problem):
    """obs=None twice is bit-identical (the default path is untouched);
    obs=on converges at the SAME iteration with the solution at the
    fusion-noise floor (arming the ring may legally re-fuse the chunk)."""
    kw = dict(strategy="esrp", T=20, rtol=1e-9,
              scenario=[FailureEvent(iter=41, nodes=(1,))])
    ra = solve_resilient(problem, **kw)
    rb = solve_resilient(problem, **kw)
    np.testing.assert_array_equal(np.asarray(ra.x), np.asarray(rb.x))
    assert ra.converged_iter == rb.converged_iter
    assert ra.trace is None

    ron = solve_resilient(problem, **kw, obs=True)
    assert ron.converged_iter == ra.converged_iter
    err = float(jnp.linalg.norm(ron.x - ra.x))
    assert err <= 1e-9 * max(float(jnp.linalg.norm(ra.x)), 1.0), err


# --------------------------------------------------------------------------- #
# the metrics ring vs a host-side replay
# --------------------------------------------------------------------------- #
def test_iteration_metrics_match_host_replay():
    p = build_problem("poisson2d", n_nodes=4, nx=16, ny=16)
    rep = solve_resilient(p, strategy="esrp", T=10, rtol=1e-9, obs=True)
    C = rep.converged_iter
    h = rep.trace.iter_history()
    assert h["iter"].tolist() == list(range(C))

    ops = p.solver_ops("auto")
    st = esrp.esrp_init(ops.matvec, ops.precond, p.b, dot=ops.dot)
    _, norms = esrp.run_chunk(st, ops, 10, C, None, 0, True, p.b, None,
                              False)
    np.testing.assert_allclose(h["rnorm"], np.asarray(norms), rtol=1e-12)

    # stepwise replay: flags are the Alg. 3 schedule on the pre-step j,
    # rz/orth are the post-step invariants
    for j in range(C):
        push_f, star_f = esrp.storage_flags(st.pcg.j, 10)
        st, _ = esrp.run_chunk(st, ops, 10, 1, None, 0, True, p.b, None,
                               False)
        assert h["push"][j] == float(bool(push_f)), j
        assert h["star"][j] == float(bool(star_f)), j
        np.testing.assert_allclose(h["rz"][j], float(st.pcg.rz), rtol=1e-12)
        # orth = |r^T p - rz| is pure cancellation noise on a clean run:
        # assert the floor, not the exact value (re-fusion moves ulps)
        assert 0 <= h["orth"][j] <= 1e-8 * max(abs(h["rz"][j]), 1e-300), j


def test_history_survives_rollback_dedup(traced):
    """Rolled-back iterations are re-recorded; the history keeps exactly one
    row per iteration with the re-run (later) values winning."""
    h = traced.trace.iter_history()
    assert h["iter"].tolist() == list(range(traced.converged_iter))
    n_push = int(round(float(np.sum(h["push"]))))
    assert n_push > 0
    # the cumulative counter also saw the pushes REDONE on rolled-back
    # stretches (physically repeated traffic), so it bounds the deduped
    # history from above in whole per-push units
    per_push = span_tree(traced.trace.events)[0]["args"]["per_push_bytes"]
    total = traced.trace.counters["tier_push_bytes"]
    assert per_push > 0 and total % per_push == 0
    assert total >= n_push * per_push


# --------------------------------------------------------------------------- #
# span-tree well-formedness + export round-trip (acceptance scenario)
# --------------------------------------------------------------------------- #
def test_trace_validates_and_events_nest(traced):
    tr = traced.trace
    assert validate_chrome_trace(chrome_trace(tr)) == []

    tree = span_tree(tr.events)
    assert tree and tree[0]["name"] == "solve"
    assert tree[0]["args"]["phi"] == 2
    assert tree[0]["dur_us"] is not None

    evs = [n for n in walk_spans(tree) if n["name"] == "event:fail-stop"]
    assert len(evs) == 2
    for ev in evs:
        inner = {d["name"] for d in walk_spans(ev["children"])}
        assert {"inject", "queue_fetch", "alg2_line5_offdiag",
                "alg2_line6_pff_solve", "alg2_line8_aff_solve",
                "scatter"} <= inner, inner
        (qf,) = [d for d in walk_spans(ev["children"])
                 if d["name"] == "queue_fetch"]
        assert qf["args"]["bytes"] > 0
    # recovery phases appear ONLY under their event span
    for n in walk_spans(tree):
        if n["name"].startswith("alg2_"):
            assert n["cat"] == "recovery"
    assert tr.counters["tier_fetch_bytes"] > 0

    its = [e for e in tr.events
           if e["name"] == "iteration" and e["ph"] == "C"]
    assert len(its) >= traced.converged_iter
    assert all("iter" in e["args"] and "rnorm" in e["args"] for e in its)


# --------------------------------------------------------------------------- #
# program spans on the profiler's clock, and the report counters read from them
# --------------------------------------------------------------------------- #
FAILSTOP = dict(strategy="esrp", T=10, phi=1, rtol=1e-8,
                scenario=[FailureEvent(iter=28, nodes=(1,))])


def _host_spans(profile):
    """{thread line: [(name, start_ns, end_ns)]} of the host plane's
    ``repro.*`` spans, and every XLA:CPU op interval."""
    spans, ops = {}, []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith("repro."):
                    spans.setdefault(line.name, []).append((e.name, *iv))
                elif line.name.startswith("tf_XLAPjRtCpuClient"):
                    ops.append(iv)
    return spans, ops


def test_program_spans_land_in_a_profile_without_a_tracer(problem, tmp_path):
    solve_resilient(problem, **FAILSTOP)           # compiles outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = solve_resilient(problem, **FAILSTOP)
    finally:
        jax.profiler.stop_trace()
    assert rep.trace is None and rep.converged
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans, ops = _host_spans(jax.profiler.ProfileData.from_file(str(path)))
    # every phase on one thread: the one that called the solver
    (thread,) = [evs for evs in spans.values()
                 if any(n == "repro.solve" for n, _, _ in evs)]
    by = {}
    for name, t0, t1 in thread:
        by.setdefault(name, []).append((t0, t1))
    want = {"repro.solve", "repro.prologue", "repro.chunk_settle",
            "repro.event:fail-stop", "repro.scatter", "repro.epilogue"}
    assert want <= set(by), sorted(by)
    ((s0, s1),) = by["repro.solve"]
    for name, ivs in by.items():
        for t0, t1 in ivs:
            assert s0 <= t0 <= t1 <= s1, name
    ((e0, e1),) = by["repro.event:fail-stop"]
    ((c0, c1),) = by["repro.scatter"]
    assert e0 <= c0 <= c1 <= e1
    ((p0, p1),) = by["repro.prologue"]
    assert p1 <= min(t0 for t0, _ in by["repro.chunk_dispatch"])
    # the device work of the solve runs inside its span
    inside = [iv for iv in ops if s0 <= iv[0] and iv[1] <= s1]
    assert inside, "no XLA:CPU op inside repro.solve"


@pytest.mark.parametrize("strategy", ["esrp", "imcr"])
def test_event_report_counters_match_the_spans(problem, strategy):
    rep = solve_resilient(problem, **dict(FAILSTOP, strategy=strategy),
                          obs=True)
    (er,) = rep.events
    (ev,) = [n for n in walk_spans(span_tree(rep.trace.events))
             if n["name"] == "event:fail-stop"]
    line8 = [n for n in walk_spans(ev["children"])
             if n["name"] == "alg2_line8_aff_solve"]
    if strategy == "esrp":
        (l8,) = line8
        assert er.inner_iters == l8["args"]["inner_iters"] > 0
    else:
        assert line8 == [] and er.inner_iters == 0
    assert er.event_s >= er.recovery_s > 0
    # event_s runs on past the event span, to the end of the resume step
    assert er.event_s > ev["dur_us"] / 1e6
    assert rep.to_json()["events"][0]["inner_iters"] == er.inner_iters


def test_runtime_is_the_solve_span(problem, monkeypatch):
    """runtime_s is the duration of the ``solve`` span, which closes after
    the final iterate is on the device; the prologue is a part of it."""
    waited = {}
    real = jax.block_until_ready

    def block(x):
        out = real(x)
        waited[id(x)] = time.perf_counter()
        return out

    monkeypatch.setattr(jax, "block_until_ready", block)
    tr = Tracer("t")
    rep = solve_resilient(problem, **FAILSTOP, obs=tr)
    (root,) = span_tree(tr.events)
    assert root["name"] == "solve"
    assert rep.runtime_s == pytest.approx(root["dur_us"] / 1e6, abs=1e-9)
    solve_end = tr._clock0 + (root["ts"] + root["dur_us"]) / 1e6
    assert waited[id(rep.x)] <= solve_end
    assert [c["name"] for c in root["children"]][-1] == "epilogue"
    (pro,) = [c for c in root["children"] if c["name"] == "prologue"]
    assert rep.prologue_s == pytest.approx(pro["dur_us"] / 1e6, abs=1e-9)
    assert 0 < rep.prologue_s < rep.runtime_s
    off = solve_resilient(problem, **FAILSTOP)
    assert 0 < off.prologue_s < off.runtime_s


def test_span_without_a_tracer_times_and_records_nothing():
    with span("phase", "solver", None, big=list(range(3))) as sp:
        assert sp.t1_us is None
    assert sp.dur_s >= 0 and sp.args == {"big": [0, 1, 2]}
    tr = Tracer("t")
    with span("phase", "solver", tr, n=1) as sp:
        pass
    assert tr.current is None
    (node,) = span_tree(tr.events)
    assert node["name"] == "phase" and node["args"] == {"n": 1}
    assert sp.dur_s == pytest.approx(node["dur_us"] / 1e6)


def test_export_round_trip(traced, tmp_path):
    tr = traced.trace
    path = write_chrome_trace(tr, str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert validate_chrome_trace(doc) == []
    assert len(doc["traceEvents"]) == len(tr.events)
    assert doc["metadata"]["schema_version"] == 1
    assert doc["metadata"]["counters"]["tier_push_bytes"] > 0

    jl = write_jsonl(tr, str(tmp_path / "events.jsonl"))
    lines = [json.loads(ln) for ln in open(jl)]
    assert lines[0]["type"] == "meta"
    assert sum(ln["type"] == "event" for ln in lines) == len(tr.events)
    assert any(ln["type"] == "solve_report" for ln in lines)

    snap = metrics_snapshot(tr)
    assert "obs_span_seconds_total" in snap
    assert 'name="solve"' in snap


def test_tracer_close_unwinds_nested_spans():
    tr = Tracer("t")
    outer = tr.begin("outer")
    tr.begin("inner")
    tr.begin("deeper")
    tr.close(outer, done=True)            # must close deeper+inner first
    assert validate_chrome_trace(chrome_trace(tr)) == []
    (root,) = span_tree(tr.events)
    assert root["args"]["done"] is True
    assert [c["name"] for c in root["children"]] == ["inner"]


# --------------------------------------------------------------------------- #
# report serialization (satellite: to_json powers the BENCH writers)
# --------------------------------------------------------------------------- #
def test_solve_report_to_json(traced):
    d = traced.to_json()
    assert d["schema_version"] == REPORT_SCHEMA_VERSION
    assert "x" not in d and "trace" not in d
    assert d["converged"] is True
    assert d["converged_iter"] == traced.converged_iter
    assert len(d["events"]) == 2
    for e in d["events"]:
        assert e["schema_version"] == REPORT_SCHEMA_VERSION
        assert e["kind"] == "fail-stop"
    # strictly JSON-safe: no device arrays, no NaN/inf anywhere
    json.dumps(d, allow_nan=False)
