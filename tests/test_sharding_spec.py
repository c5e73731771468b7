"""Paper-notation sharding specs (Shard/Replicate/Partial) — §3.1."""
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.core.mesh import MeshTopo
from repro.core.sharding import (PARTIAL_SUM, REPLICATE, Shard, ShardingSpec,
                                 spec)

TOPO = MeshTopo((("tp1", 2), ("tp2", 4)))


class TestPaperFigure4:
    """Figure 4: sharding a 2D tensor on DeviceMesh(2,2)."""

    def test_shard1_shard0(self):
        # [Shard(1), Shard(0)]: column-split at level 1, row-split at level 2
        s = spec(("tp1", "tp2"), Shard(1), Shard(0))
        assert s.partition_spec(2) == P("tp2", "tp1")

    def test_replicate_shard0(self):
        s = spec(("tp1", "tp2"), REPLICATE, Shard(0))
        assert s.partition_spec(2) == P("tp2")

    def test_row_first_weight(self):
        # W: [Shard(0), Shard(1)] (paper Fig. 5 left)
        s = spec(("tp1", "tp2"), Shard(0), Shard(1))
        assert s.partition_spec(2) == P("tp1", "tp2")

    def test_local_shape(self):
        s = spec(("tp1", "tp2"), Shard(0), Shard(1))
        assert s.local_shape(TOPO, (8, 8)) == (4, 2)

    def test_both_levels_same_dim_stack(self):
        # two mesh levels splitting the same tensor dim
        s = spec(("tp1", "tp2"), Shard(0), Shard(0))
        assert s.partition_spec(2) == P(("tp1", "tp2"))
        assert s.local_shape(TOPO, (8, 8)) == (1, 8)

    def test_partial_cannot_materialize(self):
        s = spec(("tp1", "tp2"), PARTIAL_SUM, Shard(1))
        with pytest.raises(ValueError):
            s.partition_spec(2)
        assert s.partial_axes() == ("tp1",)

    def test_indivisible_rejected(self):
        s = spec(("tp1", "tp2"), Shard(0), Shard(1))
        with pytest.raises(ValueError):
            s.local_shape(TOPO, (7, 8))


@given(d0=st.integers(0, 2), d1=st.integers(0, 2))
@settings(max_examples=20, deadline=None)
def test_local_shape_product_invariant(d0, d1):
    """prod(local) * prod(shard counts) == prod(global) for any placement."""
    s = spec(("tp1", "tp2"), Shard(d0), Shard(d1))
    g = (8, 8, 8)
    loc = s.local_shape(TOPO, g)
    counts = s.shard_counts(TOPO, 3)
    import math
    assert math.prod(loc) * math.prod(counts) == math.prod(g)

@pytest.mark.parametrize("d1,d2", [(2, 2), (4, 1)])
def test_sharded_init_places_shards_and_matches_unsharded(devices8, d1, d2):
    """Params created under jit with the param shardings as outputs: every
    device holds exactly its spec's share of each leaf, and the gathered
    values are bit-identical to the unsharded init for the same key."""
    import jax
    import numpy as np

    from repro.configs.registry import get_config
    from repro.core.mesh import atp_topo
    from repro.launch.serve import init_serving_params
    from repro.launch.steps import resolve_ctx
    from repro.models import lm

    cfg = get_config("qwen1.5-0.5b").reduced()
    topo = atp_topo(1, d1, d2)
    key = jax.random.PRNGKey(0)
    params = init_serving_params(cfg, key, topo=topo)
    ref = lm.init_params(cfg, key)
    specs = lm.param_specs(cfg, resolve_ctx(topo, None, decode=True))
    sizes = dict(topo.axes)
    flat_p, tree = jax.tree.flatten(params)
    flat_s = tree.flatten_up_to(specs)
    assert any(s != P() for s in flat_s), "nothing is sharded"
    for leaf, want, sp in zip(flat_p, jax.tree.leaves(ref), flat_s):
        share = list(leaf.shape)
        for dim, entry in enumerate(sp):
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                share[dim] //= sizes.get(ax, 1) if ax else 1
        shards = leaf.addressable_shards
        assert len(shards) == topo.size
        assert all(s.data.shape == tuple(share) for s in shards)
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))
