"""One run of one cell: set up, solve back to back for ``--seconds``, check
every answer against the plain reference, print one JSON line.

The timed path is the program's entry ``repro.core.driver.solve_resilient``
on the configuration's problem, one right-hand side per call. Each solve is
timed on the host clock from its call until its answer is on the device
(the entry blocks on it before it returns).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import generator, reference, spec
from bench import trace as trace_mod
from bench.peaks import peaks

WINDOW = "bench.window"
SOLVE = "bench.solve"


def process_age() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


class NoChip(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py", description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------- #
# device and set-up
# --------------------------------------------------------------------------- #
def use_cache(root: Path) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout,
    for every program whatever its compile time."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX finds only {info['platform']} devices")
    if require_chip and info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{info['count']}")
    return info


def build(cell: spec.Cell):
    from repro.sparse.matrices import build_problem

    cfg, tile = cell.config, int(cell.layout["tile"])
    return build_problem(
        cfg["operator"], n_nodes=int(cfg["n_nodes"]), bm=tile, bn=tile,
        precond_block=int(cfg["precond_block"]), precond=cfg["precond"],
        dtype=np.dtype(cfg["dtype"]),
        **spec.operator(cell).program_args(cfg))


def solve(problem, cell: spec.Cell, rhs, failures):
    """The timed path: one call of the program's entry."""
    from repro.core import driver
    from repro.core.failures import FailureEvent

    cfg = cell.config
    return driver.solve_resilient(
        problem, strategy=cfg["strategy"], T=int(cfg["T"]),
        phi=int(cfg["phi"]), rtol=float(cfg["rtol"]),
        backend=cell.layout["backend"], rhs=rhs,
        scenario=[FailureEvent(it, nodes) for it, nodes in failures])


class Compiles:
    """Counts XLA backend compilations (a cache hit is not one) and their
    seconds. JAX keeps a listener for the life of the process, so there is
    one, and a phase reads its compilations as a difference of counts."""
    n = 0
    seconds = 0.0
    _on = False

    @classmethod
    def listen(cls) -> None:
        if cls._on:
            return
        import jax

        def hit(event, duration, *_args, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.n += 1
                cls.seconds += duration
        jax.monitoring.register_event_duration_secs_listener(hit)
        cls._on = True


# --------------------------------------------------------------------------- #
# window
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Solve:
    rhs_index: int
    seconds: float
    report: object = None          # SolveReport, None when it raised


def window(problem, cell, pool, failures, seconds: float) -> list[Solve]:
    """Solve back to back; the last solve to start inside ``seconds`` ends."""
    import jax

    out: list[Solve] = []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW):
        while not out or time.perf_counter() - t0 < seconds:
            i = len(out)
            k = i % len(pool)
            t = time.perf_counter()
            rep = None
            with jax.profiler.TraceAnnotation(SOLVE):
                try:
                    rep = solve(problem, cell, pool[k], failures)
                    if rep.x is not None:
                        jax.block_until_ready(rep.x)
                except Exception as e:      # an answer that never comes
                    log(f"[window] solve {i} raised {type(e).__name__}: {e}")
            out.append(Solve(k, time.perf_counter() - t, rep))
    return out


# --------------------------------------------------------------------------- #
# correctness
# --------------------------------------------------------------------------- #
def stored_iteration(cfg: dict, iteration: int) -> int:
    """The iteration whose state the configuration's strategy recovers
    exactly after a fail-stop at ``iteration``: that of the newest stage or
    checkpoint completed before it, kT with k >= 1, plus the strategy's
    ``stored_iteration_offset`` (ESRP rebuilds kT + 1, the newer of its
    storage stage's two iterations; IMCR restores its checkpoint of kT).
    -1 (a restart) before the first."""
    t = int(cfg["T"])
    k = (iteration - 1) // t
    return k * t + int(cfg["stored_iteration_offset"]) if k >= 1 else -1


@dataclasses.dataclass
class Reference:
    """The plain reference of a cell: the operator as a float64 CSR matrix
    and its block-Jacobi inverses, built from the operator's formula, and
    the reference solve's answers, one for each right-hand side judged."""
    cell: spec.Cell
    a: object
    pinv: object
    answers: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def build(cls, cell: spec.Cell) -> "Reference":
        import jax.numpy as jnp

        cfg = cell.config
        a = spec.operator(cell).csr(cfg)
        pinv = jnp.asarray(
            reference.block_jacobi_inverse(a, int(cfg["precond_block"])),
            np.dtype(cfg["dtype"]))
        return cls(cell, a, pinv)

    def answer(self, key, b) -> tuple:
        """(x, iterations) of the reference solve of ``b`` at the
        configuration's precision; ``key`` names ``b`` for reuse."""
        if key not in self.answers:
            cfg = self.cell.config
            x, k, _ = reference.solve(
                spec.operator(self.cell), cfg, self.pinv, b,
                float(cfg["rtol"]), cfg["matmul_precision"])
            self.answers[key] = (np.asarray(x, np.float64), k)
        return self.answers[key]


def check(cell: spec.Cell, solves: list[Solve], pool, failures,
          ref: Reference | None = None, keys=None) -> tuple:
    """Every answer of the window against the plain reference (built here
    where ``ref`` is None; ``keys[i]`` names right-hand side ``i`` of
    ``pool`` in ``ref``'s answers). Returns (numbers compared:
    {name: (value, limit)}, failed answers)."""
    cfg = cell.config
    limit = {k: v["limit"] for k, v in spec.limits(cell).items()}
    ref = ref or Reference.build(cell)
    keys = keys or list(range(len(pool)))
    rtol = float(cfg["rtol"])
    worst = dict(true_residual_max=0.0, x_error_max=0.0,
                 extra_iters_max=-np.inf)
    count = dict(unanswered=0, unconverged=0, unrecovered=0)
    failed = 0
    for s in solves:
        rep = s.report
        if rep is None or rep.x is None:
            count["unanswered"] += 1
            failed += 1
            continue
        b = np.asarray(pool[s.rhs_index])
        x_ref, k_ref = ref.answer(keys[s.rhs_index], b)
        x = np.asarray(rep.x, np.float64)
        own = {
            "true_residual_max": reference.true_residual(ref.a, x, b),
            "x_error_max": float(np.linalg.norm(x - x_ref)
                                 / np.linalg.norm(x_ref)),
            "extra_iters_max": int(rep.converged_iter) - k_ref,
        }
        for k, v in own.items():
            worst[k] = max(worst[k], v) if np.isfinite(v) else float("inf")
        conv = bool(rep.converged) and rep.rel_residual < rtol
        struck = [(e.iter, tuple(e.nodes), e.target_iter)
                  for e in rep.events if e.kind == "fail-stop"]
        rec = struck == [(it, nodes, stored_iteration(cfg, it))
                         for it, nodes in failures]
        count["unconverged"] += not conv
        count["unrecovered"] += not rec
        failed += not (conv and rec and all(
            np.isfinite(v) and v <= limit[k] for k, v in own.items()))
    if worst["extra_iters_max"] == -np.inf:     # no answer to count
        worst["extra_iters_max"] = np.inf
    numbers = {k: (v, limit[k]) for k, v in {**worst, **count}.items()}
    return numbers, failed


def control_report(x, iterations: int, rel: float, failures, cfg: dict):
    """A report of the reference solve put in the program's place, as the
    check reads one: converged, and recovered as the configuration
    guarantees from each scheduled fail-stop, so that only its answer and
    its iteration count are judged."""
    from types import SimpleNamespace

    events = [SimpleNamespace(kind="fail-stop", iter=it, nodes=nodes,
                              target_iter=stored_iteration(cfg, it))
              for it, nodes in failures]
    return SimpleNamespace(x=x, converged=True, rel_residual=rel,
                           converged_iter=iterations, events=events,
                           wasted_iters=0, run_calls=0, recovery_s=0.0)


# --------------------------------------------------------------------------- #
# per-layer readers' context
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Context:
    cell: spec.Cell
    solves: list[Solve]
    trace: object                  # trace.Summary, or None
    geometry: dict
    peaks: dict

    def reports(self) -> list:
        return [s.report for s in self.solves if s.report is not None]

    def kernel_roofline(self, name: str):
        """100 x the least time of the kernel's calls at the chip's peak
        over their summed device time; None where the trace has none."""
        if self.trace is None:
            return None
        k = spec.kernel(self.cell, name)
        if k.BOUND != "bytes":
            raise ValueError(f"kernel {name}: bound {k.BOUND!r}")
        secs, calls = trace_mod.matching(self.trace.ops, k.NAME)
        if calls == 0 or secs <= 0:
            return None
        least = calls * k.required_bytes(self.geometry) \
            / self.peaks["hbm_bytes_per_s"]
        return 100.0 * least / secs


def traced(fn, log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #
def run(args, root: Path, require_chip: bool) -> dict:
    import jax

    cell = spec.resolve(args.workload, root)
    jax.config.update("jax_enable_x64", True)
    dev = device_info(cell.chips, require_chip)
    cache = use_cache(root)
    log(f"[device] {dev} jax {jax.__version__} cache {cache}")
    Compiles.listen()
    cfg = cell.config
    pk = peaks(dev["kind"]) if require_chip else None

    t = time.perf_counter()
    problem = build(cell)
    log(f"[setup] build {time.perf_counter() - t:.3f} s: M={problem.m}")
    op = spec.operator(cell)
    pool, warm = generator.rhs_pool(cell.traffic, op, cfg, args.seed,
                                    problem.m, np.dtype(cfg["dtype"]))
    jax.block_until_ready((pool, warm))
    failures = generator.failure_schedule(cell.traffic)
    t, n0, s0 = time.perf_counter(), Compiles.n, Compiles.seconds
    solve(problem, cell, warm, failures)
    log(f"[setup] warm-up solve {time.perf_counter() - t:.3f} s, "
        f"{Compiles.n - n0} compiles taking {Compiles.seconds - s0:.3f} s; "
        f"failures {failures}")
    compiles0 = Compiles.n
    setup_s = process_age()

    go = lambda: window(problem, cell, pool, failures, args.seconds)
    summary = None
    if args.trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            solves = traced(go, log_dir)
            summary = trace_mod.summarize(
                jax.profiler.ProfileData.from_file(
                    trace_mod.xplane_file(log_dir)), WINDOW,
                trace_mod.device_lines(dev["platform"]))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
    else:
        solves = go()
    in_window = Compiles.n - compiles0
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    geometry = {"m": problem.m, "nnz": op.nnz(cfg),
                "nnz_upper": op.nnz_upper(cfg), "symmetric": op.SYMMETRIC,
                "block": int(cfg["precond_block"]),
                "itemsize": np.dtype(cfg["dtype"]).itemsize}
    del problem
    gc.collect()

    t = time.perf_counter()
    numbers, failed = check(cell, solves, pool, failures)
    log(f"[check] {time.perf_counter() - t:.3f} s")
    correct = all(np.isfinite(v) and v <= lim for v, lim in numbers.values())
    done = [s for s in solves if s.report is not None]
    if args.trace:
        ctx = Context(cell, solves, summary, geometry, pk)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(cell, m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        tts = sum(s.seconds for s in done) / max(len(done), 1)
        values = {"time_to_solution_s": tts, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    log(f"[window] {len(solves)} solves, "
        f"{[round(s.seconds, 4) for s in solves]} s, compiles in the "
        f"window: {in_window}")
    result = {"correct": correct, "attempted": len(solves), "failed": failed,
              "metrics": metrics, "device": dev}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": [list(g) for g in
                                             summary.idle[:10]]}
    result["compiles_in_window"] = in_window
    result["checks"] = {k: {"value": v if np.isfinite(v) else None,
                            "limit": lim} for k, (v, lim) in numbers.items()}
    for k, (v, lim) in numbers.items():
        log(f"[check] {k} {v!r} limit {lim!r}")
    return result


def main(argv=None, root: Path = spec.ROOT, require_chip: bool = True) -> int:
    args = parse(argv)
    try:
        result = run(args, root, require_chip)
    except NoChip as e:
        log(f"[device] {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
