"""Serving launcher. Default mode: the streaming resilient SOLVER service —
a request queue of right-hand sides micro-batched through the batched
``solve_resilient`` (per-member convergence freeze, failures injected under
load, per-request latency spans):

    PYTHONPATH=src python -m repro.launch.serve --requests 32 --batch 8 \
        --fail-at 30 --fail-nodes 1 --trace

``--arch`` switches to the legacy language-model path (batched prefill +
decode loop). On a TPU pod the mesh comes from ``make_production_mesh`` and
the KV caches shard per the adaptive policy in ``repro.models.layers``; on
CPU it serves the reduced configs end-to-end:

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --smoke --batch 4 --prompt-len 32 --new-tokens 64 --trace
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _write_trace(tracer, metrics_out=None):
    from repro.obs import metrics_snapshot, write_chrome_trace
    os.makedirs("artifacts/obs", exist_ok=True)
    path = write_chrome_trace(tracer, "artifacts/obs/serve_trace.json")
    snap = metrics_snapshot(tracer)
    metrics_path = metrics_out or "artifacts/obs/serve_metrics.txt"
    with open(metrics_path, "w") as fh:
        fh.write(snap)
    print(f"[serve] wrote {path} + {metrics_path}")
    print(snap, end="")


def run_solver(args):
    from repro.core.failures import FailureEvent
    from repro.serve.solver_service import SolverService
    from repro.sparse.matrices import build_problem

    jax.config.update("jax_enable_x64", True)
    problem = build_problem(args.problem, n_nodes=args.n_nodes, nx=args.nx)
    scenario = None
    if args.fail_at is not None:
        nodes = tuple(int(s) for s in args.fail_nodes.split(","))
        scenario = [FailureEvent(args.fail_at, nodes)]

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer("serve")
        tracer.meta.update(mode="solver", problem=args.problem,
                           n_nodes=args.n_nodes, nx=args.nx,
                           batch=args.batch, requests=args.requests,
                           strategy=args.strategy, T=args.T, phi=args.phi)

    svc = SolverService(problem, batch=args.batch, strategy=args.strategy,
                        T=args.T, phi=args.phi, rtol=args.rtol,
                        backend=args.backend, scenario=scenario,
                        fail_every=args.fail_every, obs=tracer,
                        max_queue_wait_s=args.max_queue_wait,
                        max_retries=args.max_retries,
                        degrade=args.degrade)
    rng = np.random.default_rng(args.seed)
    print(f"[serve] solver service: {args.requests} requests over "
          f"{args.problem} n={problem.part.m} (B={args.batch}, "
          f"strategy={args.strategy}"
          + (f", failures@{args.fail_at} every {args.fail_every} "
             f"micro-batches" if scenario else "") + ")")
    t0 = time.time()
    for _ in range(args.requests):
        svc.submit(rng.standard_normal(problem.part.m),
                   deadline_s=args.deadline)
        if args.arrival_every:
            # staggered arrivals: the queue-wait bound decides when a
            # partial micro-batch beats waiting for fill
            time.sleep(args.arrival_every)
        while svc.ready():
            svc.step()
    svc.run()                                  # drain the tail
    wall = time.time() - t0
    st = svc.stats()
    print(f"[serve] {st['requests']} served in {wall:.2f}s "
          f"({st['throughput_rps']:.2f} req/s solve-side) | latency p50 "
          f"{st['latency_p50_ms']:.0f} ms p99 {st['latency_p99_ms']:.0f} ms "
          f"| {st['microbatches']} micro-batches, mean fill "
          f"{st['mean_fill']:.1f}, all_converged={st['all_converged']}")
    if args.max_queue_wait is not None or args.deadline is not None \
            or args.max_retries or args.degrade:
        print(f"[serve] deadline policy: queue-wait p99 "
              f"{st['queue_wait_p99_ms']:.0f} ms | deadline-miss rate "
              f"{st['deadline_miss_rate']:.3f} ({st['deadline_missed']} "
              f"missed) | {st['partial_dispatches']} partial dispatches | "
              f"{st['retries_total']} retries, {st['failed']} failed | "
              f"serving on {st['final_n_nodes']} nodes")
    if tracer is not None:
        _write_trace(tracer, args.metrics_out)
    return st


def run_lm(args):
    from repro.configs import get_config, smoke_config
    from repro.launch import mesh as mesh_lib
    from repro.models.lm import LM
    from repro.serve.serve_step import make_decode_step, make_prefill_step

    if args.mesh != "none":
        m = mesh_lib.make_production_mesh(multi_pod=args.mesh == "multi")
        mesh_lib.activate(m, args.mesh == "multi")   # serve keeps 2d profile

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = LM(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    print(f"[serve] {cfg.name}: {model.count_params(params) / 1e6:.1f}M "
          f"params, batch {args.batch}")

    max_len = args.prompt_len + args.new_tokens
    caches = model.init_cache(args.batch, max_len)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab,
        dtype=jnp.int32)
    prefill = jax.jit(make_prefill_step(model))
    decode = jax.jit(make_decode_step(model))

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer("serve")
        tracer.meta.update(arch=cfg.name, batch=args.batch,
                           prompt_len=args.prompt_len,
                           new_tokens=args.new_tokens, mesh=args.mesh)

    def span(name, **sargs):
        if tracer is None:
            import contextlib
            return contextlib.nullcontext()
        return tracer.span(name, cat="serve", **sargs)

    t0 = time.time()
    with span("prefill", batch=args.batch, prompt_len=args.prompt_len):
        tok, caches = prefill(params, {"tokens": prompts}, caches)
        jax.block_until_ready(tok)
    t_prefill = time.time() - t0
    t1 = time.time()
    with span("decode", new_tokens=args.new_tokens - 1):
        for i in range(args.new_tokens - 1):
            pos = jnp.asarray(args.prompt_len + i, jnp.int32)
            tok, caches = decode(params, tok, caches, pos)
            if tracer is not None:
                tracer.counter("tokens_decoded",
                               tokens=args.batch * (i + 1))
        jax.block_until_ready(tok)
    t_decode = time.time() - t1
    n_new = args.batch * (args.new_tokens - 1)
    print(f"[serve] prefill {args.batch}x{args.prompt_len} in "
          f"{t_prefill * 1000:.0f} ms; decode {n_new} tokens in "
          f"{t_decode:.2f}s ({n_new / t_decode:.1f} tok/s)")

    if tracer is not None:
        tracer.add_counter("tokens_total", n_new)
        _write_trace(tracer, args.metrics_out)


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    # shared
    ap.add_argument("--batch", type=int, default=8,
                    help="solver micro-batch width B / LM serving batch")
    ap.add_argument("--trace", action="store_true",
                    help="span-trace the run; writes "
                         "artifacts/obs/serve_trace.json + serve_metrics.txt")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="with --trace: write the text metrics snapshot "
                         "here instead of artifacts/obs/serve_metrics.txt")
    # solver service (default mode)
    ap.add_argument("--problem", default="poisson2d")
    ap.add_argument("--nx", type=int, default=32)
    ap.add_argument("--n-nodes", type=int, default=8)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--strategy", default="esrp",
                    choices=["esrp", "imcr", "none"])
    ap.add_argument("--T", type=int, default=20)
    ap.add_argument("--phi", type=int, default=1)
    ap.add_argument("--rtol", type=float, default=1e-8)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a FailureEvent at this iteration of every "
                         "fail-every'th micro-batch")
    ap.add_argument("--fail-nodes", default="1",
                    help="comma-separated node ids for --fail-at")
    ap.add_argument("--fail-every", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    # deadline-aware front-end
    ap.add_argument("--max-queue-wait", type=float, default=None,
                    metavar="S",
                    help="dispatch a partial micro-batch once the oldest "
                         "queued request has waited this long (None = "
                         "greedy dispatch)")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="per-request deadline in seconds; expired requests "
                         "end deadline_missed instead of blocking")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="retries (with backoff) for a micro-batch whose "
                         "solve dies on an unsurvivable event")
    ap.add_argument("--degrade", action="store_true",
                    help="keep serving on the elastically shrunk mesh "
                         "after an unreplaced node loss")
    ap.add_argument("--arrival-every", type=float, default=0.0, metavar="S",
                    help="stagger request arrivals by this many seconds "
                         "(exercises the queue-wait dispatch policy)")
    # LM path
    ap.add_argument("--arch", default=None,
                    help="serve a language model instead of the solver")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "single", "multi"])
    args = ap.parse_args()

    if args.arch:
        run_lm(args)
    else:
        run_solver(args)


if __name__ == "__main__":
    main()
