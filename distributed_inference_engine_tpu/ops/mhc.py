"""Manifold-constrained hyper-connections (mHC): the residual of a layer is
``n`` streams ``X [..., n, D]``, and every sublayer F reads, writes and mixes
them through three maps computed FROM X:

    x~ = vec(X) / sqrt(mean(vec(X)^2) + eps)            (no learned scale)
    m  = x~ phi  in R^(n^2 + 2n), split m_pre (n) | m_post (n) | m_res (n^2)
    H_pre  = sigmoid(alpha_1 m_pre + b_pre)
    H_post = 2 sigmoid(alpha_2 m_post + b_post)
    H_res  = Sinkhorn(exp(clip(alpha_3 mat(m_res) + b_res, lo, hi)))
    h  = sum_i H_pre[i] X[i];   y = F(RMSNorm(h))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

Sinkhorn-Knopp, ``iters`` rounds: M <- M / (row sums + hc_eps), then M <- M /
(column sums + hc_eps); rows index the stream written, columns the stream
read. Everything here is float32 whatever the activation dtype, and the
``phi`` product runs at the highest matmul precision (on a TPU a float32
product at the default precision is made of bfloat16 passes).

Layout: the maps are kept with the TOKEN axis last (``H_res [n, n, N]``): a
token's 4 x 4 matrix would otherwise pad to a whole 8 x 128 tile. Row and
column sums are adds of static slices, so the 2 x ``iters`` normalisations
are element-wise and fuse into one loop; they are unrolled here, not a
device loop of tiny ops.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Maps = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]

# ``init_hc``'s biases: normal with these spreads, plus ``RES_SHIFT`` on the
# entries of b_res that move stream j to stream j + 1 (mod n)
PRE_POST_SPREAD, RES_SPREAD, RES_SHIFT = 6.0, 3.0, 4.0


def sinkhorn(m: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    """m [n, n, N] positive -> doubly stochastic after ``iters`` rounds."""
    n = m.shape[0]
    for _ in range(iters):
        m = m / (sum(m[:, j] for j in range(n))[:, None] + eps)
        m = m / (sum(m[i] for i in range(n))[None] + eps)
    return m


def hc_maps(spec, hc: Dict[str, jnp.ndarray], x: jnp.ndarray) -> Maps:
    """x [N, n, D] float32 -> (H_pre [n, N], H_post [n, N], H_res [n, n, N])."""
    n = spec.hc_mult
    flat = x.reshape(x.shape[0], -1)
    xt = flat * lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                          + spec.norm_eps)
    m = jnp.einsum("nk,kj->jn", xt, hc["phi"],
                   precision=lax.Precision.HIGHEST)            # [n^2+2n, N]
    a, b = hc["alpha"], hc["bias"][:, None]
    pre = jax.nn.sigmoid(a[0] * m[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(a[2] * m[2 * n:] + b[2 * n:],
                           spec.hc_clamp_min, spec.hc_clamp_max))
    res = sinkhorn(res.reshape(n, n, -1), spec.hc_sinkhorn_iters, spec.hc_eps)
    return pre, post, res


def hc_read(x: jnp.ndarray, pre: jnp.ndarray) -> jnp.ndarray:
    """h [N, D] = sum_i H_pre[i] X[i]."""
    return sum(pre[i][:, None] * x[:, i] for i in range(x.shape[1]))


def hc_write(x: jnp.ndarray, y: jnp.ndarray, post: jnp.ndarray,
             res: jnp.ndarray) -> jnp.ndarray:
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y; y [N, D] any dtype."""
    n = x.shape[1]
    y = y.astype(jnp.float32)
    return jnp.stack(
        [sum(res[i, j][:, None] * x[:, j] for j in range(n))
         + post[i][:, None] * y for i in range(n)], axis=1)


def init_hc(spec, key) -> Dict[str, jnp.ndarray]:
    """One sublayer's parameters. NOT the papers' near-identity init, where
    a wrong map cannot be told from the right one by the tokens it yields:
    b_pre / b_post with spread 6 (a sublayer reads from some streams and
    writes to some, so the streams differ); b_res with spread 3 plus a
    shift of 4 towards the next stream (H_res an ASYMMETRIC doubly
    stochastic matrix that needs its Sinkhorn rounds: rows whose largest
    entries share a column); ``alpha m`` with spread ~0.5 around them (x~
    has unit RMS over n D entries, so m has spread 0.02 sqrt(n D)), so the
    maps depend on the input. Set from chip readings of served chains
    against H_res transposed and one Sinkhorn round
    (``perfbench/reference/xing4_mhc.py``): at a spread of 1 neither moved
    a served token more than bfloat16 rounding does."""
    n = spec.hc_mult
    k_phi, k_b = jax.random.split(key)
    spread = jnp.concatenate([jnp.full((2 * n,), PRE_POST_SPREAD),
                              jnp.full((n * n,), RES_SPREAD)])
    shift = jnp.zeros((n, n)).at[(jnp.arange(n) + 1) % n,
                                 jnp.arange(n)].set(RES_SHIFT)
    return {
        "phi": 0.02 * jax.random.normal(
            k_phi, (n * spec.d_model, n * n + 2 * n), jnp.float32),
        "alpha": jnp.full((3,), 0.2, jnp.float32),
        "bias": spread * jax.random.normal(k_b, (n * n + 2 * n,),
                                           jnp.float32)
        + jnp.concatenate([jnp.zeros((2 * n,)), shift.reshape(-1)]),
    }
