"""Tiny cells for the CPU tests: the real jobs and harness, on sizes a
test run can hold."""
from __future__ import annotations

import copy
import json
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent


def _load(*parts):
    with open(BENCH.joinpath(*parts)) as f:
        return json.load(f)


def tiny_config(name: str) -> dict:
    c = _load("configs", f"{name}.json")
    # hidden 512: weights of the scale the cells' have, so that a step's
    # change is some bf16 ulps, as at the cells' sizes
    c.update(hidden_size=512, intermediate_size=1024, num_hidden_layers=2,
             vocab_size=512)
    if "head_dim" in c:
        c.update(head_dim=64, num_attention_heads=8, num_key_value_heads=4)
    else:
        c.update(num_attention_heads=8, num_key_value_heads=8)
    return c


#: cells with a workload file that BENCHMARK.json does not list yet: the
#: four-chip cell is prepared and tested here, and listed once measured
UNLISTED = {"qwen3-8b.train_tp4": {"name": "qwen3-8b.train_tp4",
                                   "config": "qwen3-8b",
                                   "traffic": "train_4x1024", "chips": 4}}


def entry_of(cell: str) -> dict:
    bench = _load("..", "BENCHMARK.json")
    return copy.deepcopy(next((w for w in bench["workloads"]
                               if w["name"] == cell), None) or UNLISTED[cell])


def tiny_cell(cell: str, seed: int = 7, seconds: float = 1.0, devices=None):
    import jax

    from bench.run import Cell

    entry = entry_of(cell)
    workload = _load("workloads", f"{cell}.json")
    traffic = _load("traffic", f"{entry['traffic']}.json")
    if workload["job"] == "train":
        traffic.update(batch=4, seq=32)
        workload.update(trace_steps=2)
    else:
        traffic.update(clients=4, pool=16,
                       prompt=dict(traffic["prompt"], median=24, min=4, max=40),
                       output=dict(traffic["output"], median=6, min=2, max=8))
        workload.update(slots=4, page_size=4, pages_per_slot=16,
                        num_pages=1 + 4 * 16, prefill_chunk=8,
                        trace_skip_ticks=1, trace_ticks=4, sample_tokens=16)
    devs = devices if devices is not None else jax.devices()[: entry["chips"]]
    return Cell(name=cell, entry=entry, workload=workload,
                config=tiny_config(entry["config"]), traffic=traffic,
                seed=seed, seconds=seconds, trace=False, devices=devs,
                t_start=time.perf_counter())
