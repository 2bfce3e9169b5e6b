"""The one-hop neighbourhood M_i(t) (paper Eq. 9), in either of two formats.

* **dense**: ``mask`` ``[N, N]`` bool, ``ids`` ``None``: column ``j`` is
  node ``j``;
* **lists**: ``mask`` ``[N, K]`` bool over fixed-width neighbour lists,
  ``ids`` ``[N, K]`` int32 node ids (ascending, DESIGN.md §11).

The decisions (Eqs. 10-13 and the baselines) are written once against the
three operations below; this type is the only code in ``core`` that knows
which format it holds.  With K below the true degree the lists are a
different model (a truncated degree), not a faster dense one.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class Neighborhood(NamedTuple):
    mask: jax.Array                   # [N, M] bool: slot is a live link
    ids: Optional[jax.Array] = None   # [N, K] int32 node ids; None = dense

    def view(self, x: jax.Array) -> jax.Array:
        """``[N, M]``: node vector ``x`` ``[N]`` seen from each slot."""
        return x[None, :] if self.ids is None else x[self.ids]

    def node(self, col: jax.Array) -> jax.Array:
        """``int32 [N]``: the node id of each row's chosen column (pinned:
        argmin/argmax yield i64 under x64, and the strategy switch needs
        branch-identical avals, J002)."""
        if self.ids is None:
            return col.astype(jnp.int32)
        return self.ids[jnp.arange(self.mask.shape[0]), col]

    def masked(self, alive: jax.Array) -> "Neighborhood":
        """Down nodes have no links in either direction."""
        return self._replace(
            mask=self.mask & alive[:, None] & self.view(alive))
