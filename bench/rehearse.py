"""Compile each cell's programs at their real size for a described TPU
v5e, without a chip, and print what each needs of a device's memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

For a ``serve_closed`` cell: the paged step at both shapes the server
calls (a prefill chunk and a decode tick).  For a ``train`` cell: the
train step on the cell's plan (the searched one where the cell says
``"plan": "search"``).  Nothing runs; a program that does not fit is
refused here as it would be on the chip.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path[0] = str(CHECKOUT)
sys.path.insert(1, str(CHECKOUT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def shaped(tree, specs, mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                          sharding=s),
                        tree, shard)


def report(what: str, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"{what}: arguments {m.argument_size_in_bytes} output "
          f"{m.output_size_in_bytes} aliased {m.alias_size_in_bytes} temp "
          f"{m.temp_size_in_bytes} -> {total} bytes per device", flush=True)


def serve(cell, entry, topo_devices):
    import jax
    import jax.numpy as jnp

    from bench.model import Sizes, load_config, make_weights, program_config
    from repro.core.mesh import atp_topo
    from repro.launch.steps import build_paged_step
    from repro.models import lm
    from repro.models.paging import PagedConfig

    w = cell
    c = load_config(entry["config"])
    cfg = program_config(c)
    topo = atp_topo(1, w["plan"]["d1"], w["plan"]["d2"])
    mesh = topo.build(topo_devices)
    pcfg = PagedConfig(page_size=w["page_size"], num_pages=w["num_pages"],
                       pages_per_slot=w["pages_per_slot"])
    step, info = build_paged_step(cfg, topo, paged_cfg=pcfg, mesh=mesh)
    params = shaped(jax.eval_shape(lambda: make_weights(
        Sizes.from_file(c), jnp.zeros((2,), jnp.uint32))), info.pspecs, mesh)
    caches, cspecs = lm.init_paged_caches(cfg, info.ctx, pcfg, abstract=True)
    caches = shaped(caches, cspecs, mesh)
    mp = pcfg.pages_per_slot
    for name, b, s in (("prefill chunk", 1, w["prefill_chunk"]),
                       ("decode tick", w["slots"], 1)):
        args = (jax.ShapeDtypeStruct((b, s), jnp.int32),
                jax.ShapeDtypeStruct((b,), jnp.int32),
                jax.ShapeDtypeStruct((b, mp), jnp.int32))
        report(f"{entry['name']} {name} [{b}, {s}]",
               step.lower(params, *args, caches).compile())


def train(cell, entry, topo_devices):
    import jax
    import jax.numpy as jnp

    from bench.jobs.train import build_plan
    from bench.model import Sizes, load_config, make_weights, program_config
    from bench.traffic import load_traffic
    from repro.launch.steps import build_train_step
    from repro.optim import adamw

    c = load_config(entry["config"])
    cfg = program_config(c)
    t = load_traffic(entry["traffic"])

    plan = build_plan(cell["plan"], entry["chips"], cfg, t["batch"], t["seq"])
    print(f"{entry['name']}: plan {plan.describe()}; predicted "
          f"{plan.predicted.to_dict() if plan.predicted else None}", flush=True)
    topo = plan.topo()
    mesh = topo.build(topo_devices)
    opt_cfg = adamw.AdamWConfig(**cell["optimizer"])
    step, info = build_train_step(cfg, topo, opt_cfg, mesh=mesh, plan=plan)
    w = jax.eval_shape(lambda: make_weights(Sizes.from_file(c),
                                            jnp.zeros((2,), jnp.uint32)))
    params = shaped(w, info.pspecs, mesh)
    opt = shaped(jax.eval_shape(lambda p: adamw.init_opt_state(
        p, info.pspecs, info.ctx, opt_cfg.mode), w), info.ospecs, mesh)
    batch = shaped({k: jax.ShapeDtypeStruct((t["batch"], t["seq"]), jnp.int32)
                    for k in ("tokens", "labels")}, info.bspecs, mesh)
    report(f"{entry['name']} train step", step.lower(params, opt, batch).compile())
    reference_steps(cell, entry, c, t, topo_devices)


def reference_steps(cell, entry, c, t, topo_devices):
    """The reference's AdamW steps, spread over the cell's chips as a run
    spreads them once the program's state is freed."""
    import jax
    import jax.numpy as jnp

    from bench import reference
    from bench.model import Sizes, make_weights

    s = Sizes.from_file(c)
    w = jax.eval_shape(lambda: reference.to_f32(
        make_weights(s, jnp.zeros((2,), jnp.uint32))))
    sh = reference.shardings(w, topo_devices)
    if sh is None:
        from jax.sharding import SingleDeviceSharding

        sh = jax.tree.map(lambda _: SingleDeviceSharding(topo_devices[0]), w)
    w = jax.tree.map(lambda x, s_: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                        sharding=s_), w, sh)
    toks = jax.ShapeDtypeStruct((t["batch"], t["seq"]), jnp.int32)
    opt = cell["optimizer"]
    hyper = tuple(float(opt[k]) for k in ("b1", "b2", "eps", "weight_decay",
                                          "grad_clip"))
    n = cell["compare_steps"]
    for k in range(n):
        m = None if k == 0 else w
        v = w if k >= 2 else None
        lowered = reference._ref_step.lower(
            s, "f32", k == n - 1, w, m, v, toks, toks, jnp.float32(0), k + 1,
            hyper)
        report(f"{entry['name']} reference step {k}", lowered.compile())


def main() -> int:
    from jax.experimental import topologies

    with open(CHECKOUT / "BENCHMARK.json") as f:
        bench = json.load(f)
    names = sys.argv[1:] or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for entry in bench["workloads"]:
        if entry["name"] not in names:
            continue
        with open(CHECKOUT / "bench" / "workloads" / f"{entry['name']}.json") as f:
            cell = json.load(f)
        devices = topo.devices[: entry["chips"]]
        {"serve_closed": serve, "train": train}[cell["job"]](cell, entry, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
