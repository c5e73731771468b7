"""A configuration file, read two ways: as the program's ``ModelConfig``
and as the plain sizes the reference and the weight generator use.

The weights are the benchmark's, not the program's: :func:`make_weights`
draws them from the seed in one jitted call, in the type they are served
in, laid out as the tree the program's dense segment takes (``embed``,
``final_norm``, optional ``lm_head``, and ``seg0`` with every layer
stacked on a leading axis).  The reference reads the same tree.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the reference and the generator need of a configuration."""

    model_type: str
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool
    qkv_bias: bool
    qk_norm: bool

    @classmethod
    def from_file(cls, c: dict) -> "Sizes":
        qwen3 = c["model_type"] == "qwen3"
        return cls(
            model_type=c["model_type"], layers=c["num_hidden_layers"],
            hidden=c["hidden_size"], heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim",
                           c["hidden_size"] // c["num_attention_heads"]),
            ffn=c["intermediate_size"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
            tied=bool(c["tie_word_embeddings"]),
            qkv_bias=(not qwen3) or bool(c.get("attention_bias", False)),
            qk_norm=qwen3)

    def params_per_layer(self) -> int:
        h, qd, kvd = self.hidden, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        n = h * (qd + 2 * kvd) + qd * h + 3 * h * self.ffn + 2 * h
        if self.qkv_bias:
            n += qd + 2 * kvd
        if self.qk_norm:
            n += 2 * self.head_dim
        return n

    def head_params(self) -> int:
        """The output projection (the tied embedding when tied)."""
        return self.vocab * self.hidden

    def param_count(self) -> int:
        emb = 0 if self.tied else self.vocab * self.hidden
        return emb + self.head_params() + self.layers * self.params_per_layer() \
            + self.hidden


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def program_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry with every size the file states put over it."""
    from repro.configs.registry import get_config

    s = Sizes.from_file(c)
    base = get_config(c["registry"])
    if base.family != "dense" or base.moe or base.mla or base.ssm:
        raise ValueError(f"{c['name']}: only dense decoders are supported")
    return dataclasses.replace(
        base, num_layers=s.layers, d_model=s.hidden, num_heads=s.heads,
        num_kv_heads=s.kv_heads, head_dim=s.head_dim, d_ff=s.ffn,
        vocab_size=s.vocab, rope_theta=s.rope_theta, norm_eps=s.eps,
        tie_embeddings=s.tied, qkv_bias=s.qkv_bias, qk_norm=s.qk_norm,
        mlp_kind="swiglu", norm_kind="rmsnorm", dtype="bfloat16")


def seed_key(seed: int):
    """A JAX key from any whole number (seeds may pass 2**31)."""
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def weight_shapes(s: Sizes) -> dict:
    """{path: (shape, kind)} of the weight tree; kind picks the draw."""
    L, h, ff = s.layers, s.hidden, s.ffn
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    shapes = {
        ("embed",): ((s.vocab, h), "embed"),
        ("final_norm", "scale"): ((h,), "gain"),
        ("seg0", "ln_attn", "scale"): ((L, h), "gain"),
        ("seg0", "ln_mlp", "scale"): ((L, h), "gain"),
        ("seg0", "attn", "wq"): ((L, h, qd), "matrix"),
        ("seg0", "attn", "wk"): ((L, h, kvd), "matrix"),
        ("seg0", "attn", "wv"): ((L, h, kvd), "matrix"),
        ("seg0", "attn", "wo"): ((L, qd, h), "matrix"),
        ("seg0", "mlp", "w_up"): ((L, h, ff), "matrix"),
        ("seg0", "mlp", "w_gate"): ((L, h, ff), "matrix"),
        ("seg0", "mlp", "w_down"): ((L, ff, h), "matrix"),
    }
    if not s.tied:
        shapes[("lm_head",)] = ((h, s.vocab), "matrix")
    if s.qkv_bias:
        shapes[("seg0", "attn", "bq")] = ((L, qd), "bias")
        shapes[("seg0", "attn", "bk")] = ((L, kvd), "bias")
        shapes[("seg0", "attn", "bv")] = ((L, kvd), "bias")
    if s.qk_norm:
        shapes[("seg0", "attn", "q_norm")] = ((L, s.head_dim), "gain")
        shapes[("seg0", "attn", "k_norm")] = ((L, s.head_dim), "gain")
    return shapes


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v
    return out


def make_weights(s: Sizes, key, out_shardings=None):
    """The weight tree from ``key``, drawn on the device in one jitted call:
    matrices and biases bfloat16, norm gains float32."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(s)

    def draw(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (path, (shape, kind)) in zip(keys, sorted(shapes.items())):
            z = jax.random.normal(k, shape, jnp.float32)
            if kind == "gain":
                flat[path] = 1.0 + 0.1 * z
            elif kind == "embed":
                flat[path] = (0.02 * z).astype(jnp.bfloat16)
            elif kind == "bias":
                flat[path] = (0.1 * z).astype(jnp.bfloat16)
            else:
                flat[path] = (z / math.sqrt(shape[-2])).astype(jnp.bfloat16)
        return _nest(flat)

    return jax.jit(draw, out_shardings=out_shardings)(key)
