"""The harness finds everything by name, refuses to run without a chip,
and counts the FLOPs it says it counts."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import flops
from bench.model import Sizes, load_config

CHECKOUT = Path(__file__).resolve().parents[2]
BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_and_metric_has_its_files():
    b = CHECKOUT / "bench"
    for c in BENCH["configs"]:
        assert (CHECKOUT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert (b / "workloads" / f"{w['name']}.json").is_file()
        assert (b / "traffic" / f"{w['traffic']}.json").is_file()
        job = json.loads((b / "workloads" / f"{w['name']}.json").read_text())
        assert (b / "jobs" / f"{job['job']}.py").is_file()
    for m in BENCH["per_layer"]:
        assert (b / "metrics" / f"{m['name']}.py").is_file(), m["name"]
        for cell in m.get("workloads", []):
            e2e = [e for e in BENCH["end_to_end"] if e["name"] == m["moves"]]
            assert e2e and cell in e2e[0].get("workloads", [cell])


def test_no_result_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=CHECKOUT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name, params", [("qwen1.5-0.5b", 463_987_712),
                                          ("qwen3-8b", 3_323_584_000)])
def test_train_flops_per_token(name, params):
    s = Sizes.from_file(load_config(name))
    # 6 x (layers + final norm + head) + 12 x layers x seq x heads x hd
    assert flops.layer_params(s) + s.head_params() == params
    assert flops.train_flops_per_token(s, 1024) == \
        6 * params + 12 * s.layers * 1024 * s.heads * s.head_dim


def test_serve_step_cost_counts_real_tokens_only():
    s = Sizes.from_file(load_config("qwen1.5-0.5b"))
    f1, b1 = flops.serve_step_cost(s, [(1, 100)], 1)
    f2, b2 = flops.serve_step_cost(s, [(1, 100), (1, 300)], 2)
    per_tok = 2 * (flops.layer_params(s) - s.hidden + s.head_params())
    att = 4 * s.layers * s.heads * s.head_dim
    assert f1 == pytest.approx(per_tok + att * 101)
    assert f2 - f1 == pytest.approx(per_tok + att * 301)
    assert b2 - b1 == pytest.approx(301 * flops.kv_bytes_per_position(s))
