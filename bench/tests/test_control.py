"""The control, at a size a test run can hold: the reference computed in
float8 (the precision under the configurations' bfloat16) put in the
program's place must fail the cell's comparison; so must the reference
on half of each batch."""
import jax
import numpy as np
import pytest

from bench.control import load_cell, train_readings
from bench.model import Sizes, make_weights, seed_key
from bench.reference import serve_gaps
from bench.tests.tiny import _load, entry_of, tiny_config

SIZE = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
            vocab_size=2048, num_attention_heads=4, num_key_value_heads=4)


@pytest.mark.parametrize("name", ["qwen1.5-0.5b.train", "qwen3-8b.train_tp4"])
def test_train_control_fails(name):
    entry = entry_of(name)
    cell = {"name": name, "entry": entry,
            "workload": _load("workloads", f"{name}.json"),
            "config": dict(tiny_config(entry["config"]), **SIZE),
            "traffic": dict(_load("traffic", f"{entry['traffic']}.json"),
                            batch=4, seq=64)}
    lim = cell["workload"]["limits"]
    for seed in (1, 2):
        r = train_readings(cell, seed, jax.devices()[:1])
        for fault in ("fp8", "half_batch"):
            assert any(v > lim[n] for n, v in r[fault].items() if n in lim), \
                (fault, r)


def test_serve_control_fails():
    cell = load_cell("qwen1.5-0.5b.serve_backlog")
    s = Sizes.from_file(dict(tiny_config("qwen1.5-0.5b"), **SIZE))
    for seed in (5, 6, 7):
        w = make_weights(s, seed_key(seed))
        rng = np.random.default_rng(seed)
        seqs = []
        for n in (48, 64, 80, 96):      # 32 "served" tokens at each row's end
            toks = rng.integers(1, s.vocab, size=n, dtype=np.int32)
            seqs.append((toks, n - 33, toks[n - 32:]))
        _, ctl = serve_gaps(s, w, seqs, width=128, slots=32, control="fp8")
        worst = max(float(np.max(g)) for g in ctl)
        assert worst > cell["workload"]["limits"]["served_gap"], (seed, worst)
