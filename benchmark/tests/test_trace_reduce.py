"""trace_reduce.py on a recorded device trace.

``data/mistral-ft1_2steps.xplane.pb`` is the profiler's trace of two
steps of the `mistral-ft1` cell on one TPU v5e (my chip run, PR 22),
cut down to what the reduction reads: the chip's ``XLA Ops`` line and the
host threads' ``bench::`` and ``torchft::`` annotations, operation names
truncated to 120 characters (1.07 MB -> 64 KB; the numbers below are the
same on the full file). Plus the interval arithmetic on hand-made input.
"""

import os

import pytest

from benchmark import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "mistral-ft1_2steps.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    t = trace_reduce.reduce(TRACE, "bench::")
    assert t is not None
    return t


def test_window_is_the_traced_steps(trace):
    assert trace.chips == 1
    assert trace.window_s == pytest.approx(7.33502345, rel=1e-9)


def test_busy_union_and_idle_share(trace):
    # a lockstep FT step of a quorum of one leaves the chip idle for 91%
    assert trace.busy_s == pytest.approx(0.632850871, rel=1e-9)
    assert 1 - trace.busy_s / trace.window_s == pytest.approx(0.9137, abs=1e-4)
    # self times tile the busy union: a `while` does not own its body
    assert sum(trace.op_seconds.values()) == pytest.approx(trace.busy_s, rel=1e-6)


def test_kernel_sums_by_name(trace):
    # forward, dkv and dq flash kernels, two steps
    flash = trace.ops_matching(r"^flash_attention")
    assert flash == pytest.approx(0.053749052, rel=1e-8)
    assert len([n for n in trace.op_seconds if n.startswith("flash_attention")]) == 3
    assert trace.ops_matching(r"^no_such_kernel") == 0.0
    assert all(len(name) <= 120 for name, _ in trace.top_ops(10))


def test_gaps_are_named_for_the_open_host_span(trace):
    gaps = dict(trace.top_gaps(10))
    # the chip waits while the gradient crosses the host
    assert gaps["bench::allreduce"] == pytest.approx(6.21080673, rel=1e-8)
    assert max(gaps, key=gaps.get) == "bench::allreduce"
    assert sum(gaps.values()) == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)
    # the program's own spans are read too (per-bucket stages would be here)
    assert len(trace.host["bench::allreduce"]) == 2
    assert any(k.startswith("torchft::manager::") for k in trace.host)


def test_union_gaps_and_self_times_on_hand_made_intervals():
    assert trace_reduce.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.gaps_in([(1, 2), (1.5, 3), (5, 6)], (0, 7)) == [
        (0, 1), (3, 5), (6, 7)]
    # a wrapper of 10 s with children of 3 s and 4 s keeps 3 s for itself
    assert trace_reduce.self_times(
        [("while", 0, 10), ("a", 1, 4), ("b", 5, 9), ("a", 20, 21)]
    ) == {"while": 3, "a": 4, "b": 4}
    assert trace_reduce.short_name(
        "%fusion.9 = (f32[1,4096]{1,0}, bf16[2]) fusion(f32[4] %x), kind=kLoop"
    ).startswith("fusion.9 (f32[1,4096]")


def test_a_trace_without_a_device_gives_nothing(tmp_path):
    """A CPU run's trace has no TPU plane: there is nothing to report,
    and a CPU number never appears under a device metric's name."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.StepTraceAnnotation("bench::step", step_num=0):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    (path,) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert trace_reduce.reduce(str(path), "bench::") is None
