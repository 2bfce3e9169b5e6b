"""The two formats of ``core.neighborhood.Neighborhood`` on one graph.

A complete graph written both ways — dense ``[N, N]`` (``ids`` ``None``)
and neighbour lists with K = N − 1 (every other node, ascending) — holds
the same neighbourhoods, so every operation the decisions are written
against must agree bit for bit once the dense result is read at the
lists' ids: the per-neighbour view, the node id of a chosen column (ties
to the lowest id), the alive-masking, RandomAcyclic's visited test and
the whole of Algorithm 1 (``decision_epoch``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CongestionState, ProtocolState, decision_epoch
from repro.core.neighborhood import Neighborhood
from repro.swarm.queues import visited_neighbors, visited_words

CHECKS = ("view", "node", "masked", "visited", "decision_epoch")


def complete_graph(n):
    """The complete graph on ``n`` nodes, dense and as K = n − 1 lists."""
    dense = Neighborhood(jnp.asarray(~np.eye(n, dtype=bool)))
    ids = np.stack([np.delete(np.arange(n), i) for i in range(n)])
    lists = Neighborhood(jnp.ones((n, n - 1), bool),
                         jnp.asarray(ids, jnp.int32))
    return dense, lists, ids


def at_ids(x, ids):
    """A dense ``[n, n]`` (or broadcastable) field read at the lists' ids."""
    x = np.broadcast_to(np.asarray(x), (ids.shape[0],) * 2)
    return x[np.arange(ids.shape[0])[:, None], ids]


@pytest.mark.parametrize("check", CHECKS)
@pytest.mark.parametrize("n", (2, 12, 30, 33))
def test_complete_graph_formats_agree(n, check):
    dense, lists, ids = complete_graph(n)
    rng = np.random.default_rng(n)
    alive = jnp.asarray(rng.random(n) < 0.7)
    if check == "view":
        x = jnp.asarray(rng.uniform(0, 10, n), jnp.float32)
        np.testing.assert_array_equal(at_ids(dense.view(x), ids),
                                      np.asarray(lists.view(x)))
    elif check == "node":
        # few distinct values, so ties are common: both pick the lowest id
        U = jnp.asarray(rng.integers(0, 3, n), jnp.float32)
        got = [np.asarray(nb.node(jnp.argmin(
            jnp.where(nb.mask, nb.view(U), 1e30), axis=1)))
            for nb in (dense, lists)]
        assert got[0].dtype == got[1].dtype == np.int32
        np.testing.assert_array_equal(got[0], got[1])
    elif check == "masked":
        d, s = dense.masked(alive).mask, lists.masked(alive).mask
        assert not np.asarray(d).diagonal().any()
        np.testing.assert_array_equal(at_ids(d, ids), np.asarray(s))
        np.testing.assert_array_equal(
            np.asarray(d), ~np.eye(n, dtype=bool)
            & np.asarray(alive)[:, None] & np.asarray(alive)[None, :])
    elif check == "visited":
        words = jnp.asarray(rng.integers(0, 2 ** 32, (visited_words(n), n),
                                         dtype=np.uint32))
        np.testing.assert_array_equal(
            at_ids(visited_neighbors(words, dense), ids),
            np.asarray(visited_neighbors(words, lists)))
    else:
        F = jnp.asarray(rng.uniform(100, 500, n), jnp.float32)
        state = ProtocolState(
            jnp.asarray(rng.uniform(50, 800, n), jnp.float32),
            CongestionState(jnp.asarray(rng.uniform(0, 50, n), jnp.float32),
                            jnp.asarray(rng.uniform(0, 5, n), jnp.float32)))
        d_tx = rng.uniform(1e-4, 1e-2, (n, n)).astype(np.float32)
        kw = dict(queued_gflops=jnp.asarray(rng.uniform(0, 100, n),
                                            jnp.float32),
                  gamma=0.02, dt=0.2, alpha=0.3, tau_med=1.5, tau_high=2.5,
                  exit_points=(15, 30, 60), finalize_layers=3)
        got = [decision_epoch(state, F=F, nbhd=nb.masked(alive), d_tx=dt,
                              **kw)
               for nb, dt in ((dense, jnp.asarray(d_tx)),
                              (lists, jnp.asarray(at_ids(d_tx, ids))))]
        for a, b in zip(jax.tree.leaves(got[0]), jax.tree.leaves(got[1])):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the masked nodes keep their own capability
        phi = np.asarray(got[0].state.phi)
        np.testing.assert_array_equal(phi[~np.asarray(alive)],
                                      np.asarray(F)[~np.asarray(alive)])
