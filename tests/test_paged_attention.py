"""The paged decode attention kernel (interpret mode) against the path it
replaces: ``paging.gather_pages`` of the lane-dense stacked pool, then
``layers.attention_core`` for the one query row of each slot."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.mesh import atp_topo
from repro.kernels import ops
from repro.launch.serve import init_serving_params, make_paged_server
from repro.models import layers as L
from repro.models import paging
from repro.runtime.server import ServerConfig

#: pages of 16 positions; 160 a slot, the serve cell's page-table width
PAGE, MP = 16, 160


def _gather_attention(cfg, q, kp, vp, table, lengths, layer, window):
    b, hq, hd = q.shape
    kk = paging.gather_pages(kp, table, layer)
    vv = paging.gather_pages(vp, table, layer)
    banks = kk.shape[-1] // hd
    kk = kk.reshape(kk.shape[:2] + (banks, hd))
    vv = vv.reshape(vv.shape[:2] + (banks, hd))
    return L.attention_core(cfg, q[:, None], kk, vv, q_offset=lengths - 1,
                            kv_len=lengths, window=window)[:, 0]


@pytest.mark.parametrize(
    "hq,banks,hd,layer,window,softcap,dtype",
    [(16, 16, 64, 0, 0, 0.0, jnp.float32),      # MHA, qwen1.5-0.5b heads
     (8, 2, 128, 0, 0, 0.0, jnp.float32),       # GQA 8/2
     (16, 16, 64, 2, 0, 0.0, jnp.float32),      # a later layer of the stack
     (8, 2, 128, 1, 100, 0.0, jnp.float32),     # sliding window
     (16, 16, 64, 1, 0, 30.0, jnp.float32),     # softcap
     (8, 2, 128, 2, 40, 50.0, jnp.float32),     # window and softcap
     (16, 16, 64, 1, 0, 0.0, jnp.bfloat16)],    # the serving dtype
    ids=["mha", "gqa", "layer2", "window", "softcap", "window_softcap",
         "bf16"])
def test_paged_kernel_matches_gather(hq, banks, hd, layer, window, softcap,
                                     dtype):
    """Slot lengths 1, 15, 16, 17, mid-block and the full table width;
    unmapped entries point at the garbage page, which holds noise that a
    missing mask would let through."""
    lengths = np.array([1, 15, 16, 17, 300, MP * PAGE], np.int32)
    b = len(lengths)
    rng = np.random.default_rng(hq + layer + window)
    pages = 1 + b * MP
    shape = (3, pages, PAGE, banks * hd)
    kp = jnp.asarray(rng.standard_normal(shape), dtype)
    vp = jnp.asarray(rng.standard_normal(shape), dtype)
    q = jnp.asarray(rng.standard_normal((b, hq, hd)), dtype)
    table = np.full((b, MP), paging.GARBAGE_PAGE, np.int32)
    perm = rng.permutation(np.arange(1, pages))
    for s, n in enumerate(lengths):
        used = -(-n // PAGE)
        table[s, :used] = perm[s * MP: s * MP + used]
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b"), num_heads=hq,
                              num_kv_heads=banks, head_dim=hd,
                              attn_softcap=softcap)
    got = ops.paged_attention(q, kp, vp, table, lengths, jnp.int32(layer),
                              jnp.int32(window), softcap=softcap,
                              interpret=True)
    # the reference in float32 over the same values: a bf16 gather path
    # would round its output on its own, one bf16 step from the kernel's
    f32 = lambda x: x.astype(jnp.float32)
    want = _gather_attention(cfg, f32(q), f32(kp), f32(vp), table, lengths,
                             layer, window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-3, atol=5e-3)


def test_server_reports_decode_path():
    """A server built for the CPU gathers, and says so."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    topo = atp_topo(1, 1, 1)
    scfg = ServerConfig(batch_slots=2, prefill_chunk=8,
                        paged=paging.PagedConfig(page_size=8, num_pages=9,
                                                 pages_per_slot=4))
    params = init_serving_params(cfg, jax.random.PRNGKey(0), topo=topo)
    server, info = make_paged_server(cfg, scfg, params, topo=topo)
    assert info.decode_attention == "gather"
    assert server.stats()["decode_attention"] == "gather"
