"""The per-layer readers on hand-built traces with known answers, and
their silence on a trace of a program that lacks what they read."""
import pytest

from bench import trace as tr
from bench.run import Reading, reader


def _op(a, b, scope="", name="fusion.1", cat="loop fusion"):
    return tr.Op(a, b, name, cat, scope, f"%{name} = f32[8] {cat}(f32[8] %x)")


def _ar(a, b, scope=""):
    return _op(a, b, scope, "all-reduce-start.3", "all-reduce")


def _read(name, ops, spans, window=(0, 100)):
    t = tr.Trace(ops=ops, spans=sorted(spans, key=lambda s: s.start))
    return reader(name)(Reading(trace=t, window=window, counters={},
                                peak={}, chips=len(ops)))


def test_scope_shares():
    ops = {"d0": [_op(0, 30, "jit(f)/seg0:dense/block:attn/attn:core/dot"),
                  _op(30, 40, "jit(f)/transpose(jvp(seg0:dense))/block:attn/"
                              "attn:core/dot"),
                  _op(40, 80, "jit(f)/seg0:dense/block:mlp/atp:col[tp2]/dot"),
                  _op(90, 100, "jit(f)/opt:adamw/mul")]}
    # attention 40 of busy 90; adamw 10 of 90
    assert _read("attention_share.train", ops, []) == pytest.approx(400 / 9)
    assert _read("optimizer_share.train", ops, []) == pytest.approx(100 / 9)
    # a program without these scopes reads nothing
    bare = {"d0": [_op(0, 50, "jit(f)/seg0:dense/dot")]}
    assert _read("attention_share.train", bare, []) is None
    assert _read("optimizer_share.train", bare, []) is None


def test_boundary_exposed_share():
    ops = {
        "d0": [_op(0, 40, "jit(f)/seg0:dense/block:mlp/atp:col[tp2]/dot"),
               _ar(30, 60, "jit(f)/seg0:dense/block:mlp/atp:col[tp2]"),
               # a collective outside the boundaries: not counted
               _ar(60, 70, "jit(f)/opt:adamw"),
               _ar(80, 90, "jit(f)/transpose(jvp(seg0:dense))/block:attn/"
                           "atp:row[tp1]/...k,kn->...n")],
        "d1": [_op(0, 100, "jit(f)/seg0:dense/dot")],
    }
    # d0: 40-60 and 80-90 exposed (30); d1 none; over 2 x 100
    assert _read("boundary_exposed_share.train", ops, []) == \
        pytest.approx(100 * 30 / 200)
    # the parent's name stacks hold no atp: scope
    plain = {"d0": [_ar(30, 60, "jit(f)/seg0:dense")]}
    assert _read("boundary_exposed_share.train", plain, []) is None


def _serve_trace():
    """Two ticks of 50 us; the device runs from 20 to 40 us into each,
    inside the step call's read span."""
    spans, ops = [tr.Span(0, 100, "bench.window")], []
    for t0 in (0, 50):
        spans += [tr.Span(t0, t0 + 50, "serve.tick"),
                  tr.Span(t0, t0 + 5, "serve.sched.admit"),
                  tr.Span(t0 + 5, t0 + 10, "serve.sched.prefill"),
                  tr.Span(t0 + 10, t0 + 48, "serve.sched.decode"),
                  tr.Span(t0 + 12, t0 + 15, "serve.call.upload"),
                  tr.Span(t0 + 15, t0 + 18, "serve.call.dispatch"),
                  tr.Span(t0 + 18, t0 + 45, "serve.call.read")]
        ops.append(_op(t0 + 20, t0 + 40))
    return {"d0": ops}, spans


def test_serve_idle_split():
    ops, spans = _serve_trace()
    # per tick: idle inside the calls 12-20 and 40-45 = 13 us
    assert _read("call_idle_ms.serve", ops, spans) == pytest.approx(13e-3)
    # per tick: idle inside sched spans outside the calls 0-12, 45-48
    assert _read("sched_idle_ms.serve", ops, spans) == pytest.approx(15e-3)
    # the parent has only the benchmark's own spans
    old = [s for s in spans if not s.name.startswith(("serve.sched.",
                                                      "serve.call."))]
    assert _read("call_idle_ms.serve", ops, old) is None
    assert _read("sched_idle_ms.serve", ops, old) is None


def test_compile_counts():
    ops = {"d0": [_op(0, 10)]}
    window = [tr.Span(0, 100, "bench.window")]
    steps = [tr.Span(10 * k, 10 * k + 5, "train.loop.dispatch")
             for k in range(1, 4)]
    assert _read("compiles.train", ops, window + steps) == 0
    marks = [tr.Span(30, 31, "train.loop.compile"),
             tr.Span(26, 26, "train.loop.recompile"),
             tr.Span(120, 121, "train.loop.recompile")]    # past the window
    assert _read("compiles.train", ops, window + steps + marks) == 2
    assert _read("compiles.train", ops, window) is None

    calls = [tr.Span(10, 12, "serve.call.dispatch"),
             tr.Span(12, 12, "serve.call.recompile")]
    assert _read("compiles.serve", ops, window + calls[:1]) == 0
    assert _read("compiles.serve", ops, window + calls) == 1
    assert _read("compiles.serve", ops, window) is None
