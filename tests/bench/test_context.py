"""The run's context: the configuration fixes the graph, ``--seed`` draws
the traffic on it."""
import numpy as np
import pytest

from bench.harness import STREAM_KEYS, Context
from harness_faults import tiny

FIELDS = ("indptr", "indices", "src", "dst", "indptr_t", "indices_t")


def context(config, seed):
    return Context(config=config, traffic={}, seed=seed, seconds=1.0,
                   trace=False, t_start=0.0)


@pytest.mark.parametrize("cell", ["g500_s20_bfs64", "kron_s15_p2p"])
def test_seeds_share_the_graph_and_draw_their_own_traffic(cell):
    _, config, _ = tiny(cell)
    a, b = context(config, 7), context(config, 2**33 + 5)
    ga, gb = a.graph(), b.graph()
    assert (ga.n_nodes, ga.n_edges, ga.m_pad) == \
        (gb.n_nodes, gb.n_edges, gb.m_pad)
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(ga, f)),
                                      np.asarray(getattr(gb, f)), f)
    live = np.flatnonzero(np.diff(np.asarray(ga.indptr)) > 0)
    order_a = a.rng(STREAM_KEYS).permutation(live)
    order_b = b.rng(STREAM_KEYS).permutation(live)
    assert not np.array_equal(order_a, order_b)
    np.testing.assert_array_equal(
        order_a, context(config, 7).rng(STREAM_KEYS).permutation(live))
    # what the deployment fixes beside its graph, such as a job's batches
    np.testing.assert_array_equal(a.job_rng(STREAM_KEYS).permutation(live),
                                  b.job_rng(STREAM_KEYS).permutation(live))


def test_the_graph_seed_picks_the_graph():
    _, config, _ = tiny("kron_s15_apsp")
    one = context(config, 7).graph()
    two = context(dict(config, graph_seed=2), 7).graph()
    assert not np.array_equal(np.asarray(one.indices),
                              np.asarray(two.indices))


@pytest.mark.parametrize("graph_seed", [None, -1, 1.0, "1", True])
def test_a_configuration_without_a_whole_graph_seed_is_refused(graph_seed):
    _, config, _ = tiny("kron_s15_apsp")
    config = {k: v for k, v in config.items() if k != "graph_seed"}
    if graph_seed is not None:
        config["graph_seed"] = graph_seed
    with pytest.raises(ValueError, match="graph_seed"):
        context(config, 7).graph()
