"""Data placement: virtual groups and local data hubs (paper §IV-C2).

Frozen copy of ``src/repro/core/placement.py`` and ``src/repro/core/kmeans.py``
at commit bcb7c9a: K-Means (Lloyd iterations in JAX, k-means++ seeding in
numpy) over (type, location, continent) request features, hub choice by
Eq. (2), hot objects per group.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

THETA_P = 0.6
THETA_U = 0.2
THETA_F = 0.2


@functools.lru_cache(maxsize=8)
def _compiled_lloyd(n: int, dim: int, k: int, iters: int):
    def lloyd(x, centers0):
        def step(centers, _):
            d2 = jnp.sum((x[:, None, :] - centers[None, :, :]) ** 2, axis=-1)
            assign = jnp.argmin(d2, axis=1)
            one_hot = jax.nn.one_hot(assign, k, dtype=x.dtype)
            counts = one_hot.sum(axis=0)
            sums = one_hot.T @ x
            new_centers = sums / jnp.maximum(counts[:, None], 1.0)
            new_centers = jnp.where(counts[:, None] > 0, new_centers, centers)
            return new_centers, None

        centers, _ = jax.lax.scan(step, centers0, None, length=iters)
        d2 = jnp.sum((x[:, None, :] - centers[None, :, :]) ** 2, axis=-1)
        assign = jnp.argmin(d2, axis=1)
        inertia = jnp.sum(jnp.min(d2, axis=1))
        return centers, assign, inertia

    return jax.jit(lloyd)


def _kmeanspp_init(x: np.ndarray, k: int, rng: np.random.Generator) -> np.ndarray:
    n = x.shape[0]
    centers = [x[rng.integers(n)]]
    for _ in range(1, k):
        d2 = np.min([np.sum((x - c) ** 2, axis=1) for c in centers], axis=0)
        if d2.sum() <= 0:
            centers.append(x[rng.integers(n)])
            continue
        probs = d2 / d2.sum()
        centers.append(x[rng.choice(n, p=probs)])
    return np.stack(centers)


def kmeans(x: np.ndarray, k: int, iters: int = 25, seed: int = 0):
    """Cluster rows of x into k groups: (centers, assignments, inertia)."""
    x = np.asarray(x, dtype=np.float32)
    n, dim = x.shape
    k = min(k, n)
    rng = np.random.default_rng(seed)
    centers0 = _kmeanspp_init(x, k, rng)
    fn = _compiled_lloyd(n, dim, k, iters)
    centers, assign, inertia = fn(jnp.asarray(x), jnp.asarray(centers0))
    return np.asarray(centers), np.asarray(assign), float(inertia)


@dataclasses.dataclass
class VirtualGroup:
    group_id: int
    user_ids: list[int]
    hub_dtn: int
    hot_objs: list[int]


def _request_features(reqs, grid) -> np.ndarray:
    """Feature vector per request: (instrument type, location, continent)."""
    f = np.zeros((len(reqs), 3), dtype=np.float32)
    for i, r in enumerate(reqs):
        f[i, 0] = grid.type_of(r.obj)
        f[i, 1] = grid.loc_of(r.obj)
        f[i, 2] = r.continent * grid.n_locs / 6.0
    return f


def select_hub(candidate_dtns: Sequence[int], peer_throughput: np.ndarray,
               utilization: Mapping[int, float],
               request_freq: Mapping[int, float]) -> int:
    """Eq. (2): argmax over candidate DTNs of the weighted score."""
    best, best_score = candidate_dtns[0], -np.inf
    p_sums = {i: float(np.sum(peer_throughput[i]) - peer_throughput[i, i])
              for i in candidate_dtns}
    p_max = max(p_sums.values()) or 1.0
    f_max = max((request_freq.get(i, 0.0) for i in candidate_dtns), default=1.0) or 1.0
    for i in candidate_dtns:
        score = (
            THETA_P * p_sums[i] / p_max
            + THETA_U * utilization.get(i, 0.0)
            + THETA_F * request_freq.get(i, 0.0) / f_max
        )
        if score > best_score:
            best, best_score = i, score
    return best


class PlacementEngine:
    """Periodic virtual-group clustering + hub selection + hot-data listing."""

    def __init__(self, grid, n_groups: int = 4, hot_objs_per_group: int = 8,
                 seed: int = 0):
        self.grid = grid
        self.n_groups = n_groups
        self.hot_objs_per_group = hot_objs_per_group
        self.seed = seed
        self.groups: list[VirtualGroup] = []

    def recluster(self, recent_requests, user_dtn: Mapping[int, int],
                  peer_throughput: np.ndarray,
                  utilization: Mapping[int, float]) -> list[VirtualGroup]:
        if not recent_requests:
            self.groups = []
            return self.groups
        feats = _request_features(recent_requests, self.grid)
        k = min(self.n_groups, max(1, len({r.user_id for r in recent_requests})))
        _, assign, _ = kmeans(feats, k, seed=self.seed)
        groups: list[VirtualGroup] = []
        for g in range(k):
            reqs_g = [r for r, a in zip(recent_requests, assign) if a == g]
            if not reqs_g:
                continue
            users = sorted({r.user_id for r in reqs_g})
            dtns = sorted({user_dtn.get(u, 0) for u in users})
            freq = collections.Counter(user_dtn.get(r.user_id, 0) for r in reqs_g)
            hub = select_hub(dtns, peer_throughput, utilization,
                             {d: float(c) for d, c in freq.items()})
            obj_pop = collections.Counter(r.obj for r in reqs_g)
            hot = [o for o, _ in obj_pop.most_common(self.hot_objs_per_group)]
            groups.append(VirtualGroup(g, users, hub, hot))
        self.groups = groups
        return groups
