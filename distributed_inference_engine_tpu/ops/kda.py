"""The gated delta rule, its state ``S [dk, dv]`` per head in float32:

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

Two gates run through the SAME functions, told apart by the last axis of
``g``: Kimi Delta Attention (KDA, ``models/ling.py``) has one decay per
head AND key channel (``g [..., H, dk]``, ``kda_gate``); Gated DeltaNet
(``models/olmo_hybrid.py``) one per head (``g [..., H, 1]``, ``gdn_gate``),
which broadcasts over the key channels wherever the per-channel one
multiplies. ``dk`` and ``dv`` need not be equal (Gated DeltaNet: 96 keys
against 192 values) and ``b`` may lie anywhere in (0, 2): the transition's
eigenvalue along ``k`` is ``1 - b``, down to -1.

Three forms of the same recurrence:

- ``kda_step``: one token (decode). Elementwise float32 on the VPU, so the
  state never passes through a reduced-precision matmul.
- ``kda_scan``: token by token over a sequence. The tests' oracle.
- ``kda_chunked``: the served prefill. Inside a chunk of ``chunk`` tokens
  the recurrence is the WY form: with ``G_i`` the running sum of ``g`` and
  ``A_ij = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])`` (j < i), the pseudo
  values ``U = (I + Diag(b) A)^-1 Diag(b) (V - (K * exp(G)) S_0)`` come from
  one unit-lower-triangular solve (forward substitution in exact float32);
  ``o_i = S_0^T (q_i * exp(G_i)) + sum_{j<=i} B_ij u_j`` with ``B`` as ``A``
  but from ``q_i``; chunks are joined by a scan that carries ``S`` in float32.

``exp(G_i - G_j)`` must not be formed from ``exp(G_i)`` and ``exp(-G_j)``
alone: at g near the lower bound -5 a 64-token chunk spans e^-320. Each
sub-block of ``sub`` = 16 rows is re-based on the running sum at its first
row R: ``exp(G_i - R) <= 1`` for the rows of the block, ``exp(R - G_j) <= 1``
for every earlier column, and inside the block ``exp(R - G_j) <= e^75``,
finite in float32. Columns after a row are masked (their clamped factor is
finite too). That bound is KDA's (``kda_gate``'s ``lower_bound``); Gated
DeltaNet's gate ``-exp(A_log) softplus(.)`` has none, and a head that decays
by e^-10 a token overruns the clamp inside a sub-block: broadcast over the
key channels through this construction it is WRONG (``tests/test_gdn.py``:
outputs off by 0.9), not slow. So ONE decay a head takes the construction it
allows: ``A_ij = (k_i . k_j) exp(G_i - G_j)`` is a plain matrix product
times a [C, C] table whose exponents are <= 0 under the diagonal, exact for
any decay.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def kda_gate(a: jnp.ndarray, a_log: jnp.ndarray, dt_bias: jnp.ndarray,
             lower_bound: float) -> jnp.ndarray:
    """Log-decay ``g = lower_bound * sigmoid(exp(A_log_h) * a + dt_bias)``
    in (lower_bound, 0): a [..., H*dk] -> g [..., H, dk], float32."""
    h = a_log.shape[0]
    a = a.astype(jnp.float32).reshape(*a.shape[:-1], h, -1)
    z = jnp.exp(a_log.astype(jnp.float32))[:, None] * a \
        + dt_bias.astype(jnp.float32).reshape(h, -1)
    return lower_bound * jax.nn.sigmoid(z)


def gdn_gate(a: jnp.ndarray, a_log: jnp.ndarray, dt_bias: jnp.ndarray
             ) -> jnp.ndarray:
    """Log-decay ``g = -exp(A_log_h) * softplus(a + dt_bias_h)`` in
    (-inf, 0), one a head: a [..., H] -> g [..., H, 1], float32."""
    z = a.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    return (-jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(z))[
        ..., None]


def l2_normalize(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_step(S, q, k, v, g, beta) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token. S [..., dk, dv]; q, k [..., dk]; g [..., dk] or
    [..., 1]; v [..., dv]; beta [...]; all float32. Returns (o [..., dv],
    S)."""
    S = S * jnp.exp(g)[..., None]
    k_s = jnp.sum(k[..., None] * S, axis=-2)                   # k^T S
    S = S + k[..., None] * (beta[..., None] * (v - k_s))[..., None, :]
    return jnp.sum(q[..., None] * S, axis=-2), S


def kda_scan(q, k, v, g, beta, S0=None):
    """Token by token over [B, T, H, d] float32 inputs (beta [B, T, H]).
    Returns (o [B, T, H, dv], S [B, H, dk, dv])."""
    b, _t, h, dk = q.shape
    if S0 is None:
        S0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def body(S, xs):
        o, S = kda_step(S, *xs)
        return S, o

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
               for a in (q, k, v, g, beta))
    S, o = lax.scan(body, S0.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), S


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _unit_lower_solve(L: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """``(I + L)^-1 Diag(beta)`` for strictly lower-triangular ``L``
    [..., C, C], by forward substitution: row i is ``beta_i e_i - L[i] @
    rows before it``, each product an elementwise multiply and sum in
    float32. (XLA's TriangularSolve multiplies float32 blocks at the
    backend's default precision, one bfloat16 pass on a TPU.)"""
    c = L.shape[-1]
    eye = jnp.eye(c, dtype=L.dtype)

    def row(T, i):
        r = beta[..., i, None] * eye[i] - jnp.sum(
            L[..., i, :, None] * T, axis=-2)
        return T.at[..., i, :].set(r), None

    T, _ = lax.scan(row, jnp.zeros_like(L), jnp.arange(c))
    return T


def kda_chunked(q, k, v, g, beta, S0=None, chunk: int = 64, sub: int = 16):
    """The chunked form over [B, T, H, d] float32 inputs (g [B, T, H, dk]
    or [B, T, H, 1], beta [B, T, H]). T is padded here
    to a multiple of ``chunk`` with beta = 0, g = 0 rows, which leave the
    state as it was; a caller masks its own pad rows the same way.
    Returns (o [B, T, H, dv], S [B, H, dk, dv])."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, -(-t // sub) * sub)
    pad = -t % chunk
    f32 = jnp.float32

    def prep(a):
        a = a.astype(f32)
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        # [B, T, H, ...] -> [B, H, NC, C, ...]
        a = a.reshape(b, -1, chunk, *a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = (prep(a) for a in (q, k, v, g, beta))
    nc, nb = q.shape[2], chunk // sub
    # exact float32 adds: a running sum lowered to a reduced-precision
    # matmul would be off by e^0.1 in the decays it feeds
    G = lax.associative_scan(jnp.add, g, axis=3)     # [B,H,NC,C,dk or 1]
    if g.shape[-1] == 1:
        # one decay a head, unbounded below (the re-based form would overrun
        # its clamp): exp(G_i - G_j) is a [C, C] table, its exponents clamped
        # at 0 (those above are columns after the row, masked below)
        Gs = G[..., 0]
        table = jnp.exp(jnp.minimum(Gs[..., :, None] - Gs[..., None, :], 0.0))
        A = _mm("bhnic,bhnjc->bhnij", k, k) * table
        Bm = _mm("bhnic,bhnjc->bhnij", q, k) * table
    else:
        Gb = G.reshape(b, h, nc, nb, sub, dk)
        R = Gb[..., 0, :]                                      # [B,H,NC,nb,dk]
        scale = jnp.exp(Gb - R[..., None, :])                  # <= 1
        left_k = k.reshape(Gb.shape) * scale
        left_q = q.reshape(Gb.shape) * scale
        # column factor of block I's rows: k_j exp(R_I - G_j) for every j
        right = k[:, :, :, None] * jnp.exp(jnp.minimum(
            R[..., :, None, :] - G[:, :, :, None, :, :], 80.0))  # [..,nb,C,dk]
        A = _mm("bhnisc,bhnijc->bhnisj", left_k, right).reshape(
            b, h, nc, chunk, chunk)
        Bm = _mm("bhnisc,bhnijc->bhnisj", left_q, right).reshape(
            b, h, nc, chunk, chunk)
    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]
    A = jnp.where(col < row, A, 0.0)
    Bm = jnp.where(col <= row, Bm, 0.0)
    Tm = _unit_lower_solve(beta[..., None] * A, beta)          # [..,C,C]
    W = _mm("bhnij,bhnjv->bhniv", Tm, v)
    eG = jnp.exp(G)
    Y = _mm("bhnij,bhnjc->bhnic", Tm, k * eG)
    Qt = q * eG
    G_end = G[:, :, :, -1:, :]
    Kh = k * jnp.exp(G_end - G)
    decay = jnp.exp(G_end[:, :, :, 0, :])                 # [B,H,NC,dk or 1]
    if S0 is None:
        S0 = jnp.zeros((b, h, dk, dv), f32)

    def body(S, xs):
        W_c, Y_c, Q_c, B_c, K_c, d_c = xs
        U = W_c - _mm("bhic,bhcv->bhiv", Y_c, S)
        o = _mm("bhic,bhcv->bhiv", Q_c, S) + _mm("bhij,bhjv->bhiv", B_c, U)
        S = d_c[..., None] * S + _mm("bhic,bhiv->bhcv", K_c, U)
        return S, o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (W, Y, Qt, Bm, Kh, decay))
    S, o = lax.scan(body, S0.astype(f32), xs)                  # o [NC,B,H,C,dv]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, nc * chunk, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t], S


def causal_conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution over time, zero history: x [B, T, C],
    w [K, C] (tap K-1 is the current token). float32 out."""
    kk = w.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (kk - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(xp[:, i:i + t] * w[i] for i in range(kk))


def conv_step(tail: jnp.ndarray, x: jnp.ndarray, w: jnp.ndarray):
    """One token of the same convolution against the last K-1 inputs:
    tail [B, K-1, C], x [B, C] -> (y [B, C] float32, new tail)."""
    win = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    y = jnp.sum(win.astype(jnp.float32) * w.astype(jnp.float32)[None], axis=1)
    return y, win[:, 1:]


def conv_tail(x: jnp.ndarray, seq_lens: jnp.ndarray, k: int) -> jnp.ndarray:
    """The last k-1 inputs before each row's TRUE end (zeros where the row
    is shorter): x [B, T, C] -> [B, k-1, C]."""
    idx = seq_lens[:, None] - (k - 1) + jnp.arange(k - 1)[None, :]
    rows = jnp.take_along_axis(x, jnp.maximum(idx, 0)[..., None], axis=1)
    return jnp.where((idx >= 0)[..., None], rows, 0).astype(x.dtype)
