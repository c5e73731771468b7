"""Overlap engine benchmark: chunk counts x boundary modes on a simulated
8-device mesh (4x2 ATP).  Writes BENCH_overlap.json.

    PYTHONPATH=src python -m benchmarks.overlap_bench

Per config it records
  - measured wall time of one pre-norm + MLP block (CPU host mesh: the
    numbers validate plumbing, not speedups — there is no async collective
    engine on the CPU backend), and
  - the overlap-aware cost model's view on a real interconnect (IC4 flat
    IB): exposed comm time and modeled ax1/ax2 boundary wire bytes.

Acceptance properties asserted and stored in "summary":
  - sequence-parallel reduces modeled ax1 *boundary* bytes by >= 1.9x vs
    the replicated block I/O spec (reduce-scatter vs all-reduce; the
    conjugate block-entry gather is reported separately in
    ax1_total_bytes — total fwd+bwd volume is conserved, the win is
    per-op wire size, overlap granularity, and d1x activation memory);
  - whenever per-chunk GEMM time exceeds per-chunk ring time, the model
    ranks chunks > 1 strictly cheaper than chunks = 1.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "../src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_overlap.json")

D1, D2 = 4, 2
BATCH, SEQ, HIDDEN, FF = 4, 64, 256, 512
LAYERS = 2


def _modes():
    return [
        ("replicated", dict(boundary_mode="psum", seq_parallel=False)),
        ("replicated-ring", dict(boundary_mode="ring", seq_parallel=False)),
        ("seq-parallel", dict(boundary_mode="psum", seq_parallel=True)),
        ("seq-parallel-ring", dict(boundary_mode="ring", seq_parallel=True)),
    ]


def measure_block(mode_kwargs, chunks: int) -> float:
    """Wall time (us) of pre-norm + MLP (f3/f4 boundaries) on the host mesh."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.atp import atp_linear, make_context
    from repro.core.mesh import MeshTopo
    from repro.models import layers as L

    topo = MeshTopo((("tp1", D1), ("tp2", D2)))
    mesh = topo.build(jax.devices()[: topo.size])
    ctx = make_context(topo, chunks=chunks, **mode_kwargs)

    x = jax.random.normal(jax.random.PRNGKey(0), (BATCH, SEQ, HIDDEN))
    gamma = jnp.ones((HIDDEN,))
    A = jax.random.normal(jax.random.PRNGKey(1), (HIDDEN, FF)) * 0.05
    B = jax.random.normal(jax.random.PRNGKey(2), (FF, HIDDEN)) * 0.05

    def block(x, gamma, A, B):
        h = L.rms_norm(ctx, x, gamma, gather_seq=ctx.seq_parallel)
        y = jax.nn.gelu(atp_linear(ctx, h, A, kind="col"))
        return x + atp_linear(ctx, y, B, kind="row")

    seq_ax = "tp1" if ctx.seq_parallel else None
    xspec = P(None, seq_ax, "tp2")
    f = jax.jit(shard_map(
        block, mesh=mesh,
        in_specs=(xspec, P("tp2"), P("tp2", "tp1"), P("tp1", "tp2")),
        out_specs=xspec, check_vma=False))
    xs = jax.device_put(x, jax.sharding.NamedSharding(mesh, xspec))
    f(xs, gamma, A, B).block_until_ready()
    t0 = time.perf_counter()
    iters = 20
    for _ in range(iters):
        out = f(xs, gamma, A, B)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def modeled(mode_kwargs, chunks: int):
    from repro.core import comm_matrix as cm
    from repro.core.cost_model import LayerCommProfile, t_comm_overlap

    profile = LayerCommProfile(FF, HIDDEN, hidden=HIDDEN)
    c = t_comm_overlap(
        cm.ic4_ib_cluster_16gpu(), D1, D2,
        layers=LAYERS, batch=BATCH, seq=SEQ, profile=profile,
        chunks=chunks, seq_parallel=mode_kwargs["seq_parallel"],
        peak_tflops=50.0, algo="ring", alpha_s=2e-6)
    return {
        "t_comm_s": c.t_comm,
        "t_exposed_s": c.t_exposed,
        "t_gemm_s": c.t_gemm,
        "ax1_boundary_bytes": c.ax1_boundary_bytes,
        "ax1_total_bytes": c.ax1_total_bytes,
        "ax2_boundary_bytes": c.ax2_boundary_bytes,
    }


def chunk_ranking_property() -> dict:
    """Model property: chunks>1 strictly cheaper whenever per-chunk GEMM
    time exceeds per-chunk ring time (swept over payload scales)."""
    from repro.core import comm_matrix as cm
    from repro.core.cost_model import LayerCommProfile, t_comm_overlap

    checked = violations = applicable = 0
    for scale, peak in ((1, 50.0), (16, 50.0), (64, 5.0), (64, 1.0)):
        profile = LayerCommProfile(FF * scale, HIDDEN, hidden=HIDDEN * scale)
        base = t_comm_overlap(cm.ic4_ib_cluster_16gpu(), D1, D2,
                              layers=LAYERS, batch=BATCH, seq=SEQ,
                              profile=profile, chunks=1, peak_tflops=peak,
                              algo="ring", alpha_s=2e-6)
        for chunks in (2, 4, 8):
            c = t_comm_overlap(cm.ic4_ib_cluster_16gpu(), D1, D2,
                               layers=LAYERS, batch=BATCH, seq=SEQ,
                               profile=profile, chunks=chunks,
                               peak_tflops=peak, algo="ring", alpha_s=2e-6)
            checked += 1
            if c.fully_overlapped:
                applicable += 1
                if not c.t_exposed < base.t_exposed:
                    violations += 1
    return {"checked": checked, "applicable": applicable,
            "violations": violations}


def main() -> None:
    results = []
    for mode_name, kwargs in _modes():
        for chunks in (1, 2, 4):
            wall = measure_block(kwargs, chunks)
            m = modeled(kwargs, chunks)
            results.append({"mode": mode_name, "chunks": chunks,
                            "wall_us": round(wall, 1), **{"modeled": m}})
            print(f"{mode_name:>18} chunks={chunks}: {wall:8.1f} us  "
                  f"exposed={m['t_exposed_s']*1e3:.3f} ms  "
                  f"ax1_boundary={m['ax1_boundary_bytes']/1e6:.2f} MB")

    rep = next(r for r in results
               if r["mode"] == "replicated" and r["chunks"] == 1)
    sp = next(r for r in results
              if r["mode"] == "seq-parallel" and r["chunks"] == 1)
    ratio = (rep["modeled"]["ax1_boundary_bytes"]
             / sp["modeled"]["ax1_boundary_bytes"])
    ranking = chunk_ranking_property()

    summary = {
        "ax1_boundary_bytes_replicated": rep["modeled"]["ax1_boundary_bytes"],
        "ax1_boundary_bytes_seq_parallel": sp["modeled"]["ax1_boundary_bytes"],
        "ax1_boundary_reduction_x": round(ratio, 3),
        "ax1_total_bytes_seq_parallel": sp["modeled"]["ax1_total_bytes"],
        "chunk_ranking": ranking,
    }
    assert ratio >= 1.9, f"seq-parallel boundary reduction {ratio:.2f}x < 1.9x"
    assert ranking["violations"] == 0, ranking

    payload = {
        "bench": "overlap",
        "mesh": {"devices": D1 * D2, "d1": D1, "d2": D2},
        "shape": {"batch": BATCH, "seq": SEQ, "hidden": HIDDEN, "ff": FF,
                  "layers": LAYERS},
        "configs": results,
        "summary": summary,
    }
    with open(OUT_PATH, "w") as fh:
        json.dump(payload, fh, indent=2)
    print(f"summary: {json.dumps(summary)}")
    print(f"wrote {os.path.abspath(OUT_PATH)}")


if __name__ == "__main__":
    main()
