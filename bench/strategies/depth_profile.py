"""Exit depths that the traffic sets, not the logits.

`DepthProfileStrategy` is an online strategy in the repo's protocol
(`init` / `observe` / `serve`, see `repro.strategy.base`).  At node i
it lets a lane continue with probability ``p[i]``, the continue share
the traffic file gives for that node.  The draw is a hash of
(member, token index, node): a bank of ``members`` strategies is
served, each request is given the member ``rid % members``, and each
lane counts its own decode tokens from admission.  So a request's
depth at each token is a function of its id and the token's index
alone: no rounding of the logits, and no admission order, can move it.

The served node is the node the lane stopped at (no recall): the
depth the step ran is the node whose logits it serves.

`expected_nodes` recomputes the same decisions in numpy; the benchmark's
correctness check holds every served node to it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DepthProfileStrategy", "make_bank", "thresholds",
           "expected_nodes", "hash_u24"]

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D
_F1, _F2 = 0x85EBCA6B, 0xC2B2AE35


def thresholds(cont) -> np.ndarray:
    """Continue shares as 24-bit integer thresholds: continue iff
    ``hash_u24 < threshold``."""
    cont = np.asarray(cont, np.float64)
    if ((cont < 0) | (cont > 1)).any():
        raise ValueError(f"continue shares must lie in [0, 1]: {cont}")
    return np.round(cont * (1 << 24)).astype(np.int64)


def hash_u24(member, token, node, xp=np):
    """24 uniform bits from (member, token index, node): a murmur3
    finalizer over a mix of the three, in uint32 arithmetic (wraps)."""
    u32 = xp.uint32
    x = (xp.asarray(member).astype(u32) * u32(_M1)
         + xp.asarray(token).astype(u32) * u32(_M2)
         + xp.asarray(node).astype(u32) * u32(_M3))
    x = x ^ (x >> u32(16))
    x = x * u32(_F1)
    x = x ^ (x >> u32(13))
    x = x * u32(_F2)
    x = x ^ (x >> u32(16))
    return x >> u32(8)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DepthState:
    token: jax.Array         # (B,) i32 index of the lane's current token
    node: jax.Array          # (B,) i32 last node probed this token
    explore_cost: jax.Array  # (B,) f32
    n_probed: jax.Array      # (B,) i32


class DepthProfileStrategy:
    """Continue past node i with probability ``cont[i]``, decided by a
    hash of (member, token index, node)."""

    online = True
    # the token counter runs across a request's tokens; admission's
    # init_lane is its only reset
    persistent = True

    def __init__(self, cont, member: int, lam: float = 1.0):
        cont = np.asarray(cont, np.float64)
        self.n_nodes = len(cont) + 1
        self.member = int(member)
        self.lam = float(lam)
        self.cont = cont
        self._thr = jnp.asarray(
            np.append(thresholds(cont), 0).astype(np.uint32))

    def init(self, batch: int) -> DepthState:
        return DepthState(
            token=jnp.full((batch,), -1, jnp.int32),
            node=jnp.zeros((batch,), jnp.int32),
            explore_cost=jnp.zeros((batch,), jnp.float32),
            n_probed=jnp.zeros((batch,), jnp.int32))

    def observe(self, state: DepthState, node, losses, active, aux=None):
        del losses, aux   # depth is the traffic's, not the logits'
        token = jnp.where(active & (node == 0), state.token + 1,
                          state.token)
        u = hash_u24(self.member, token, node, xp=jnp)
        cont = active & (u < self._thr[node]) & (node + 1 < self.n_nodes)
        return DepthState(
            token=token,
            node=jnp.where(active, node, state.node).astype(jnp.int32),
            explore_cost=state.explore_cost,
            n_probed=state.n_probed + active), cont

    def serve(self, state: DepthState) -> jax.Array:
        return state.node


def make_bank(cont, members: int) -> tuple:
    """The bank the cell serves; request ``rid`` uses member
    ``rid % members``."""
    return tuple(DepthProfileStrategy(cont, m) for m in range(members))


def expected_nodes(cont, member: int, n_tokens: int) -> np.ndarray:
    """The node each of a request's first ``n_tokens`` decode tokens is
    served at, recomputed on the host."""
    thr = thresholds(cont)
    n_nodes = len(thr) + 1
    t = np.arange(n_tokens)
    node = np.zeros(n_tokens, np.int64)
    going = np.ones(n_tokens, bool)
    for i in range(n_nodes - 1):
        u = hash_u24(member, t, i).astype(np.int64)
        going &= u < thr[i]
        node += going
    return node
