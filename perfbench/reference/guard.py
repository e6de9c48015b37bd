"""Algorithm 1's filter (Alistarh, Allen-Zhu & Li, NeurIPS 2018) in plain
f32, with the online estimate of V that the distributed exact guard uses.

Per step k (1-based), for W workers with fresh gradients g_i, running sums
B_i = sum of g_i over steps, and A_i = sum of <g_i, x - x_1>:

    C    = log(16 W T / delta)
    T_A  = 4 D V sqrt(k C),   T_B = 4 V sqrt(k C)
    ok_A = |A_i - median(A)| <= T_A
    ok_B = ||B_i - B_med|| <= T_B,  B_med a point with more than W/2
           points within T_B (the one with the least total distance;
           the medoid when there is none)
    ok_g = ||g_i - g_med|| <= 4 V,  g_med the same at radius 2 V
    good_k = good_{k-1} and ok_A and ok_B and ok_g

V is estimated online: half the square root of the lower quartile of the
squared pairwise distances of the fresh gradients, smoothed as
V_k = 0.9 V_{k-1} + 0.1 v_k from the second step on.
"""
from __future__ import annotations

import math

import jax.numpy as jnp


def sq_dists(gram):
    diag = jnp.diagonal(gram)
    return jnp.maximum(diag[:, None] + diag[None, :] - 2.0 * gram, 0.0)


def estimate_v(gram_g, v_prev, ema: float = 0.9):
    W = gram_g.shape[0]
    iu, ju = jnp.triu_indices(W, k=1)
    v_now = 0.5 * jnp.sqrt(jnp.quantile(sq_dists(gram_g)[iu, ju], 0.25))
    v = jnp.where(v_prev > 0, ema * v_prev + (1.0 - ema) * v_now, v_now)
    return jnp.maximum(v, 1e-12)


def _dist_to_counting_median(gram, radius):
    d2 = sq_dists(gram)
    W = d2.shape[0]
    dist = jnp.sqrt(d2)
    valid = jnp.sum(d2 <= radius * radius, axis=1) * 2 > W
    total = jnp.sum(dist, axis=1)
    med = jnp.where(jnp.any(valid),
                    jnp.argmin(jnp.where(valid, total, jnp.inf)),
                    jnp.argmin(total))
    return dist[med]


def filter_step(A, gram_B, gram_g, alive, k, V, *, T: int, delta: float,
                D: float):
    """good_k from the statistics of step k; all arguments f32."""
    W = A.shape[0]
    root = jnp.sqrt(k * math.log(16.0 * W * max(T, 1) / delta))
    t_a, t_b = 4.0 * D * V * root, 4.0 * V * root
    ok_a = jnp.abs(A - jnp.median(A)) <= t_a
    ok_b = _dist_to_counting_median(gram_B, t_b) <= t_b
    ok_g = _dist_to_counting_median(gram_g, 2.0 * V) <= 4.0 * V
    return alive & ok_a & ok_b & ok_g
