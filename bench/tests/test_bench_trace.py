"""The reduction of a profiler trace to busy, idle and per-op time."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import spec, trace

KERNELS = {k: spec.load_module(spec.ROOT / f"bench/kernels/{k}.py")
           for k in ("spmv_dot", "fused_update")}


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (9, 20)], 0, 10)
    assert busy == [(0, 3), (5, 7), (9, 10)]
    assert trace.gaps(busy, 0, 10) == [(3, 5), (7, 9)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_op_seconds_and_matching():
    ops = [trace.Event("_spmv_dot_kernel.1", 0, 1e9),
           trace.Event("_spmv_dot_kernel.1", 2e9, 2.5e9),
           trace.Event("fusion.3", 3e9, 3.1e9),
           trace.Event("_spmv_dot_kernel.1", 9e9, 10e9)]     # outside
    table = trace.op_seconds(ops, 0, 5e9)
    assert table["_spmv_dot_kernel.1"] == pytest.approx((1.5, 2))
    assert trace.matching(table, r"^_spmv_dot") == pytest.approx((1.5, 2))
    assert trace.matching(table, r"^nothing") == (0, 0)
    assert trace.busy_seconds(ops, 0, 5e9) == pytest.approx(1.6)


@pytest.mark.parametrize("kernel,op", [
    ("spmv_dot", "%spmv_dot.1"), ("spmv_dot", "%spmv_dot_batched.1"),
    ("spmv_dot", "%spmv_dot"),
    ("fused_update", "%fused_pcg_update.1"),
    ("fused_update", "%fused_pcg_update_batched.1")])
def test_kernel_name_matches_named_and_unnamed_custom_call(kernel, op):
    """A ``pallas_call`` named for the kernel and the jitted wrapper's
    name, which the custom call takes without one, are both the kernel."""
    assert trace.matching({op: (2.0, 3)}, KERNELS[kernel].NAME) == (2.0, 3)


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("op", ["%spmv_batched.1", "%spmv.1", "%fusion.11",
                                "%spmv_dot_kernel.1",
                                "%fused_pcg_update_other.1"])
def test_kernel_name_leaves_other_ops_out(kernel, op):
    assert trace.matching({op: (2.0, 3)}, KERNELS[kernel].NAME) == (0, 0)


def test_label_takes_the_shortest_covering_host_span():
    host = [trace.Event("bench.solve", 0, 100),
            trace.Event("PjitFunction(run_chunk)", 40, 60)]
    assert trace.label((45, 55), host) == "PjitFunction(run_chunk)"
    assert trace.label((10, 20), host) == "bench.solve"
    assert trace.label((200, 300), host) == "(no host span)"


def test_reduce_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(5):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    profile = jax.profiler.ProfileData.from_file(
        trace.xplane_file(str(tmp_path)))
    s = trace.summarize(profile, "bench.window", trace.is_cpu_op_line)
    assert 0 < s.busy_s <= s.window_s
    assert s.ops and all(n >= 1 for _, n in s.ops.values())
    assert sum(t for _, t in s.idle) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6, abs=1e-9)
    top = s.top_ops(3)
    assert len(top) <= 3 and top[0][1] >= top[-1][1]
    with pytest.raises(ValueError, match="no host span"):
        trace.summarize(profile, "no.such.span", trace.is_cpu_op_line)
    with pytest.raises(ValueError, match="no device operations"):
        trace.summarize(profile, "bench.window", trace.is_tpu_op_line)
