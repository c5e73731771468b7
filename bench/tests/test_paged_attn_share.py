"""The reader of ``paged_attn_share.serve`` on hand-built traces: the ops
under the paged decode kernel's ``attn:paged`` scope give their share of
busy time, and a trace of the gather path, which lacks the scope, reads
nothing."""
import pytest

from bench.tests.test_metrics import _op, _read


def test_paged_attn_share():
    ops = {"d0": [_op(0, 20, "jit(f)/seg0:dense/while/body/closed_call/"
                             "block:attn/attn:paged/jit(paged_attention)/"
                             "pallas_call", "paged_attention", "custom-call"),
                  _op(20, 25, "jit(f)/seg0:dense/block:attn/attn:paged/mul"),
                  _op(30, 80, "jit(f)/seg0:dense/block:mlp/dot")]}
    # the kernel and its query 25 of busy 75
    assert _read("paged_attn_share.serve", ops, []) == pytest.approx(100 / 3)
    # the gather path's ops carry no attn:paged
    gather = {"d0": [_op(0, 20, "jit(f)/seg0:dense/block:attn/jit__take_/"
                                "gather"),
                     _op(20, 30, "jit(f)/seg0:dense/block:attn/attn:core/dot")]}
    assert _read("paged_attn_share.serve", gather, []) is None
