"""Operations and bytes of a step, from the configuration's sizes alone.

Training (PaLM, appendix B): a token costs 6 x N FLOPs forward and
backward, N the non-embedding parameters plus the output head, and the
attention scores and sums add 12 x layers x seq x heads x head_dim.
Recomputation is not counted: it is not work the model requires.

Serving counts the work a step must do, not what the program does: the
layers for every prompt or decode token that is real (not padding), the
head only where a token's logits are needed, attention over the positions
each token really sees, and the bytes of every weight once plus the K/V of
the live positions it reads and the new positions it writes.
"""
from __future__ import annotations

from bench.model import Sizes

BF16 = 2


def layer_params(s: Sizes) -> int:
    """Non-embedding parameters: the layers and the final norm."""
    return s.layers * s.params_per_layer() + s.hidden


def train_flops_per_token(s: Sizes, seq: int) -> float:
    n = layer_params(s) + s.head_params()
    return 6.0 * n + 12.0 * s.layers * seq * s.heads * s.head_dim


def weight_bytes(s: Sizes) -> int:
    """Bytes of every weight, read once per step (bf16)."""
    return s.param_count() * BF16


def kv_bytes_per_position(s: Sizes) -> int:
    return 2 * s.layers * s.kv_heads * s.head_dim * BF16


def serve_step_cost(s: Sizes, queries: list[tuple[int, int]],
                    logits: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one serve step.  ``queries``: per request row,
    (real tokens fed, positions already in its cache); ``logits``: tokens
    whose logits are needed.  Token i of a row fed at cache length c
    attends to c + i + 1 positions."""
    lp = layer_params(s) - s.hidden
    flops = 2.0 * s.head_params() * logits
    kv = 0
    per_pos = 4.0 * s.layers * s.heads * s.head_dim
    for n, c in queries:
        flops += 2.0 * lp * n + per_pos * (n * c + n * (n + 1) / 2)
        kv += c + n
    return flops, float(weight_bytes(s) + kv * kv_bytes_per_position(s))


def describe(s: Sizes, seq: int) -> str:
    return (f"train FLOPs/token {train_flops_per_token(s, seq):.6e} "
            f"(6 x {layer_params(s) + s.head_params()} params + attention at "
            f"seq {seq}); serve weight bytes {weight_bytes(s)}, "
            f"K/V bytes/position {kv_bytes_per_position(s)}, decode FLOPs/token "
            f"{2.0 * (layer_params(s) - s.hidden + s.head_params()):.6e} "
            f"+ {4 * s.layers * s.heads * s.head_dim} per attended position")
