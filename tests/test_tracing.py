"""The program's own tracing: host spans in the train loop, the scheduler
and the step wrapper, recorded by the profiler with exact names and their
identifiers as arguments; the compile counter and its recompile markers;
the name scopes on every collective of a tensor-parallel train step."""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import signature
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_config
from repro.core.mesh import atp_topo
from repro.core.plan import ParallelPlan
from repro.data.pipeline import DataConfig, TokenSource
from repro.launch.serve import init_serving_params, make_paged_server
from repro.launch.steps import batch_struct, build_train_step
from repro.models import lm
from repro.models.paging import PagedConfig
from repro.optim import adamw
from repro.runtime.server import Request, ServerConfig
from repro.runtime.trainer import Trainer, TrainerConfig

SRC = Path(__file__).resolve().parents[1] / "src"

#: the benchmark's own span names: the program never uses them
BENCH_SPANS = {"train.batch", "train.put", "train.step", "serve.tick",
               "serve.prefill", "serve.decode"}


def record(tmp_path, fn):
    """Run ``fn`` under the profiler; return its host spans named
    ``train.*`` / ``serve.*`` as dicts (name, ts, end, args), in order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.trace.json.gz"))
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    spans = [{"name": e["name"], "ts": e["ts"], "end": e["ts"] + e["dur"],
              "args": e.get("args", {})}
             for e in events if e.get("ph") == "X"
             and e["name"].startswith(("train.", "serve."))]
    return sorted(spans, key=lambda s: (s["ts"], -s["end"]))


def inside(span, outer) -> bool:
    return outer["ts"] <= span["ts"] and span["end"] <= outer["end"]


def tiny_trainer(tmp_path, total=3, ckpt_every=3, source=None):
    source = source or TokenSource(DataConfig(vocab_size=10, seq_len=4,
                                              global_batch=2))

    def build_step():
        @jax.jit
        def step(params, opt, batch):
            w = params["w"] + jnp.sum(batch["tokens"]) * 0 + 1.0
            return {"w": w}, opt, {"loss": 1.0 / (w + 1)}
        return step

    cfg = TrainerConfig(total_steps=total, ckpt_dir=str(tmp_path / "ckpt"),
                        ckpt_every=ckpt_every, max_failures=3)
    return Trainer(cfg, build_step, source,
                   lambda: ({"w": jnp.zeros(())}, {"n": jnp.zeros(())}),
                   lambda b: {"tokens": jnp.asarray(b["tokens"])})


def test_train_loop_spans(tmp_path):
    tr = tiny_trainer(tmp_path)
    fired = []

    def fail_once(step):
        if step == 1 and not fired:
            fired.append(step)
            raise RuntimeError("injected device fault")

    spans = record(tmp_path / "trace", lambda: tr.run(fail_injector=fail_once))
    names = collections.Counter(s["name"] for s in spans)
    # step 0, the fault at step 1 (before its fetch), a restore to step 0,
    # then steps 0-2; the rebuilt step compiles ahead once more
    assert names == {"train.loop.fetch": 4, "train.loop.transfer": 4,
                     "train.loop.compile": 2, "train.loop.dispatch": 4,
                     "train.loop.loss_read": 4, "train.loop.bookkeep": 4,
                     "train.loop.recover": 1, "train.loop.checkpoint": 1}
    assert not BENCH_SPANS & set(names)
    # the phases follow each other: none holds another
    for a, b in zip(spans, spans[1:]):
        assert a["end"] <= b["ts"], (a, b)
    steps = [s["args"]["step"] for s in spans
             if s["name"] == "train.loop.dispatch"]
    assert steps == ["0", "0", "1", "2"]


def test_recompile_marker_and_trainer_count(tmp_path):
    class Widening:
        """Batches of 4 tokens a row, then of 8 from step 2."""

        def global_batch(self, step):
            return {"tokens": np.zeros((2, 4 if step < 2 else 8), np.int32)}

    tr = tiny_trainer(tmp_path, total=4, ckpt_every=0, source=Widening())
    tr.init_state()          # its eager ops compile outside the count
    before = tr.compiles
    spans = record(tmp_path / "trace", tr.run)
    marks = [s for s in spans if s["name"] == "train.loop.recompile"]
    # the compile ahead of step 0, then the dispatch of step 2 at the new
    # shape; the shape of step 3 was compiled already
    assert [s["args"]["step"] for s in marks] == ["2"]
    assert tr.compiles - before == 2
    (dispatch,) = [s for s in spans if s["name"] == "train.loop.dispatch"
                   and s["args"]["step"] == "2"]
    assert dispatch["end"] <= marks[0]["ts"]


def test_scheduler_and_call_spans(tmp_path):
    cfg = get_config("qwen1.5-0.5b").reduced()
    topo = atp_topo(1, 1, 1)
    scfg = ServerConfig(batch_slots=2, prefill_chunk=8,
                        paged=PagedConfig(page_size=8, num_pages=9,
                                          pages_per_slot=4))
    params = init_serving_params(cfg, jax.random.PRNGKey(0), topo=topo)
    server, _ = make_paged_server(cfg, scfg, params, topo=topo)
    # 16 prompt tokens: two prefill chunks (ticks 0 and 1), then decode
    # ticks 1 and 2
    server.submit(Request(rid=7, prompt=np.arange(16, dtype=np.int32) + 1,
                          max_new=3))
    counts = []

    def three_ticks():
        for _ in range(3):
            counts.append(server.stats()["compiles"])
            server.step()
        counts.append(server.stats()["compiles"])

    spans = record(tmp_path, three_ticks)
    names = collections.Counter(s["name"] for s in spans)
    assert not BENCH_SPANS & set(names)
    for phase in ("admit", "prefill", "decode"):
        ticks = [s["args"]["tick"] for s in spans
                 if s["name"] == f"serve.sched.{phase}"]
        assert ticks == ["0", "1", "2"]
    for part in ("upload", "dispatch", "read"):
        assert names[f"serve.call.{part}"] == 4      # 2 chunks, 2 decodes
    sched = [s for s in spans if s["name"].startswith("serve.sched.")]
    for s in spans:
        if s["name"].startswith("serve.call."):
            (outer,) = [o for o in sched if inside(s, o)]
            is_prefill = outer["name"] == "serve.sched.prefill"
            assert outer["name"] in ("serve.sched.prefill",
                                     "serve.sched.decode")
            # a prefill chunk's call names its request; a decode's none
            assert s["args"].get("rid") == ("7" if is_prefill else None)
    # the first call at each shape compiled: one marker each, the count
    # up by one each time; the calls at a known shape compiled nothing
    marks = [s for s in spans if s["name"] == "serve.call.recompile"]
    assert len(marks) == 2
    assert [b - a for a, b in zip(counts, counts[1:])] == [1, 1, 0]
    assert server.stats()["compiles"] == counts[-1]


def _boundary_scopes(d1: int, d2: int, chunks: int):
    """Every collective's name stack in a (d1, d2) qwen3 train step."""
    paths = []
    attribution = signature._attribution

    def recording(path):
        paths.append(path)
        return attribution(path)

    cfg = get_config("qwen3-8b").reduced()
    plan = ParallelPlan(d1=d1, d2=d2, dp=1, chunks=chunks,
                        boundary_mode="psum")
    fn, info = build_train_step(cfg, plan=plan,
                                opt_cfg=adamw.AdamWConfig(mode="zero1"))
    params = lm.abstract_params(cfg)
    opt = adamw.init_opt_state(params, info.pspecs, info.ctx, "zero1",
                               abstract=True)
    batch = batch_struct(cfg, ShapeConfig("x", 32, 8, "train"), "train")
    signature._attribution = recording
    try:
        signature.extract(fn, params, opt, batch)
    finally:
        signature._attribution = attribution
    return paths


@pytest.mark.parametrize("d1,d2,chunks", [(2, 2, 1), (2, 2, 8), (4, 1, 1),
                                          (4, 1, 8)])
def test_every_collective_is_scoped(devices8, d1, d2, chunks):
    paths = _boundary_scopes(d1, d2, chunks)
    assert paths
    owners = ("atp:", "opt:adamw", "shell:")
    boundaries = 0
    for p in paths:
        bare = [signature._strip_transforms(c) for c in p]
        assert any(c.startswith(owners) for c in bare), p
        boundaries += any(c.startswith(("atp:col[", "atp:row["))
                          for c in bare)
        # the new scopes leave what the signature attributes unchanged
        old = tuple(c for c in p if not signature._strip_transforms(c)
                    .startswith(("atp:", "opt:", "block:", "attn:")))
        assert signature._attribution(p) == signature._attribution(old)
    assert boundaries > 0


#: compiles attention_core through the persistent cache of a checkout
#: given as argv[1], cache at argv[2]; prints the cache's entry count
CACHED_COMPILE = """
import os, sys
sys.path.insert(0, os.path.join(sys.argv[1], "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[2]
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from repro.configs.registry import get_config
from repro.models import layers
cfg = get_config("qwen1.5-0.5b").reduced()
q = jnp.ones((1, 8, 4, 32))
jax.jit(lambda q: layers.attention_core(cfg, q, q, q, q_offset=0))(q)
print(len(os.listdir(sys.argv[2])))
"""


def test_cache_key_follows_names_not_checkout(tmp_path):
    """The persistent cache's key holds the ops' names, so a renamed
    scope compiles anew (a trace never shows another program's names),
    and source files relative to the checkout, so a checkout moved
    elsewhere loads what it compiled before."""
    cache = tmp_path / "cache"
    for name in ("a", "b"):
        shutil.copytree(SRC, tmp_path / name / "src",
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def entries(checkout):
        p = subprocess.run([sys.executable, "-c", CACHED_COMPILE,
                            str(checkout), str(cache)], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        return int(p.stdout.split()[-1])

    first = entries(tmp_path / "a")
    assert first > 0
    assert entries(tmp_path / "b") == first
    layers_py = tmp_path / "b" / "src" / "repro" / "models" / "layers.py"
    layers_py.write_text(layers_py.read_text().replace(
        '"attn:core"', '"attn:renamed"'))
    assert entries(tmp_path / "b") > first
