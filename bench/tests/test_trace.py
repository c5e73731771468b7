"""The trace reduction on hand-built intervals with known unions and
overlaps, and on a tiny trace recorded on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace as tr


def test_union_merges_and_sorts():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]
    assert tr.length([(0, 2), (1, 3), (10, 11)]) == 4


def test_subtract_and_intersect():
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [(0, 2), (3, 5), (7, 9)]
    assert tr.intersect([(0, 4), (6, 10)], [(3, 7), (9, 20)]) == \
        [(3, 4), (6, 7), (9, 10)]
    assert tr.clip([(0, 4), (6, 10)], (2, 8)) == [(2, 4), (6, 8)]


def _op(a, b, name="fusion.1", cat="loop fusion", scope=""):
    return tr.Op(a, b, name, cat, scope, f"%{name} = f32[8] {cat}(f32[8] %x)")


def test_idle_collective_and_scope_shares():
    ops = {
        "d0": [_op(0, 40, scope="jit(f)/seg0:dense/dot_general"),
               _op(30, 60, "all-reduce.3", "all-reduce"),
               _op(70, 80, scope="jit(f)/transpose(jvp(shell:head))/dot")],
        "d1": [_op(0, 100, scope="jit(f)/shell:loss/reduce_sum")],
    }
    t = tr.Trace(ops=ops, spans=[tr.Span(0, 100, "bench.window"),
                                 tr.Span(60, 70, "train.put")])
    w = tr.window_of(t, "bench.window")
    assert w == (0, 100)
    # d0 busy 0-60 and 70-80 = 70; d1 busy 100: mean idle (30 + 0) / 2
    assert tr.idle_share(t, w) == pytest.approx(0.15)
    assert tr.busy_s(t, w) == pytest.approx(85e-6)
    coll, exposed = tr.collective_shares(t, w)
    assert coll == pytest.approx(30 / 200)       # 30 on d0, none on d1
    assert exposed == pytest.approx(20 / 200)    # 40-60 has no other op
    # head (10) + loss (100) over busy (70 + 100)
    assert tr.scope_share(t, w, ("shell:head", "shell:loss")) == \
        pytest.approx(110 / 170)
    # d0 is idle 60-70 (under train.put) and 80-100 (no span); d1 never
    assert dict(tr.idle_gaps(t, w)) == {"none": pytest.approx(10e-6),
                                        "train.put": pytest.approx(5e-6)}
    t_in, n = tr.device_time_in(t, "train.put", w)
    assert n == 1 and t_in == pytest.approx(5e-6)   # d1 busy, d0 idle


def test_container_ops_are_not_busy():
    t = tr.Trace(ops={"d0": [_op(0, 10), _op(90, 100)]}, spans=[])
    assert tr.idle_share(t, (0, 100)) == pytest.approx(0.8)


def test_recorded_cpu_trace(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("shell:head"):
            y = jnp.tanh(x @ x)
        return jnp.sum(y)

    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("train.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_trace_file(str(tmp_path)), ("bench.", "train."))
    w = tr.window_of(t, "bench.window")
    assert w is not None and w[1] > w[0]
    assert sum(s.name == "train.step" for s in t.spans) == 3
    assert t.ops, "no device op found in the CPU trace"
    assert 0 < tr.busy_s(t, w) <= (w[1] - w[0]) * 1e-6
    assert 0.0 <= tr.idle_share(t, w) < 1.0
    assert tr.top_ops(t, w)
