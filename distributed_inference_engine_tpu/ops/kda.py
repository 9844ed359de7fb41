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

Four forms of the same recurrence:

- ``kda_step``: one token, on one layer's states ``[B, H, dk, dv]`` handed
  in and given back. Elementwise float32 on the VPU, so the state never
  passes through a reduced-precision matmul. Called by ``kda_scan`` and, as
  the XLA body of the next form, wherever no TPU runs the program (the CPU
  tests and rehearsals).
- ``kda_step_inplace``: the served decode step (``models/olmo_hybrid.py``,
  ``models/ling.py``). The same arithmetic over the engine's WHOLE state
  array ``[layers, B, H, dk, dv]`` (or its lane-packed form, below), of
  which it moves one layer's live slots where they lie. On a TPU (``step_impl``) a Mosaic kernel: the array
  is aliased to the output, the layer's index a prefetched scalar (traced
  inside Gated DeltaNet's scan over periods, static in KDA's unrolled
  layers), the grid over (slot, block of whole heads); a head's ``[dk, dv]``
  state is decayed, updated by the rank-one term and read out in registers,
  each byte of it read from HBM once and written once. The mask is inside:
  a dead slot's turns are mapped to the block a live neighbour already
  holds, so nothing of a dead slot is fetched or written and its state
  stays bit for bit (XLA's form read the state for the step, selected
  ``where(active, new, old)`` over it and wrote a period's stack back:
  three to four passes, PERF.md section 6, PR 34). It stays OFF the MXU for
  the reason above: ``k^T S`` and ``S^T q`` as float32 matmuls would run as
  bfloat16 passes at the default precision, or six of them at ``highest``,
  for a step that is bound by the state's bytes either way. 192 value lanes
  are no multiple of 128, and a float32 array whose last dim is 192 is
  PADDED to 256 lanes in HBM: the engine keeps such states ``lane_pack``
  heads side by side (``pack_states``: two of 96 x 192 a row of 384 lanes),
  the kernel spreads each head's column over its own lanes of the row, and
  a block takes the array's last two dims whole.
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

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# a block of the in-place kernel holds whole rows of states, as many as fit
# this many bytes of VMEM (float32, padded to (8, 128) tiles); the pipeline
# keeps two such blocks coming in and two going out. A whole slot of either
# cell (15 x 96 x 384, 32 x 128 x 128) fits, and the largest block was the
# fastest alone on the chip: 3 / 5 / 10 / 15 / 30 heads of 96 x 192 a block
# read 98.7 / 89.2 / 88.1 / 85.2 / 82.7 us a call
# (docs/sweeps/pr34-kda-step-inplace-kernel.txt)
STEP_BLOCK_BYTES = 3 << 20


def step_impl() -> str:
    """Which body runs a decode step over the engine's state array:
    "inplace", the kernel, on a TPU; "xla" elsewhere. ("inplace_interpret":
    the kernel through the interpreter, for the CPU tests.)"""
    return "inplace" if jax.default_backend() == "tpu" else "xla"


def lane_pack(h: int, dv: int) -> int:
    """How many heads' states lie side by side along the lanes of the
    engine's state array: the fewest that fill whole 128-lane tiles. A
    float32 ``[..., 96, 192]`` array is laid out in HBM with its 192 lanes
    PADDED to 256, and every pass over it moves the padding (alone on the
    chip the kernel took 82.6 us a call at 192 lanes and 82.4 at 256); two
    heads a row are 384 lanes, no padding. 1 where ``dv`` fills its tiles
    (KDA's 128) or no count of heads does."""
    return next((m for m in range(1, h + 1)
                 if h % m == 0 and m * dv % 128 == 0), 1)


def pack_states(S: jnp.ndarray, m: int) -> jnp.ndarray:
    """[..., H, dk, dv] -> [..., H / m, dk, m * dv]: heads ``m i .. m i + m -
    1`` side by side along the lanes of row i."""
    *lead, h, dk, dv = S.shape
    S = S.reshape(*lead, h // m, m, dk, dv)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, h // m, dk, m * dv)


def unpack_states(S: jnp.ndarray, m: int) -> jnp.ndarray:
    """The inverse of ``pack_states``."""
    *lead, hp, dk, w = S.shape
    S = S.reshape(*lead, hp, dk, m, w // m)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, hp * m, dk, w // m)


def _rows_per_block(hp: int, dk: int, w: int) -> int:
    padded = -(-dk // 8) * 8 * -(-w // 128) * 128 * 4
    return max(d for d in range(1, hp + 1)
               if hp % d == 0 and (d == 1 or d * padded <= STEP_BLOCK_BYTES))


def _step_kernel(meta_ref, blk_ref, act_ref, s_ref, qkg_ref, vb_ref,
                 s_out, o_ref, *, dk: int, dv: int, m: int, rb: int):
    """Grid (slot, block of rows). ``s_ref`` / ``s_out`` [1, 1, rb, dk,
    m dv]: the SAME HBM block of the aliased state array, ``rb`` rows of
    ``m`` heads' states side by side (``blk_ref`` maps a dead slot's turns
    to the block its neighbour already holds, so the pipeline neither
    fetches nor writes anything for them); ``qkg_ref`` [1, 1, 3 dk, rb m]:
    q | k | g with the heads along the lanes, so a head's column broadcasts
    over its state's ``dv`` lanes; ``vb_ref`` [1, 1, 2, rb, m dv]: v and beta
    (broadcast over ``dv``), laid out as the rows are."""
    live = act_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        qkg = qkg_ref[0, 0]
        q, k = qkg[:dk], qkg[dk:2 * dk]
        decay = jnp.exp(qkg[2 * dk:])
        lane = lax.broadcasted_iota(jnp.int32, (1, m * dv), 1)

        def cols(x, i):
            """Row i's heads' columns of x [dk, rb m], each over its own
            head's lanes: [dk, m dv] (one column's broadcast where m = 1)."""
            out = x[:, m * i:m * i + 1]
            for j in range(1, m):
                out = jnp.where(lane >= j * dv, x[:, m * i + j:m * i + j + 1],
                                out)
            return out

        for i in range(rb):
            k_i = cols(k, i)
            S = s_ref[0, 0, i] * cols(decay, i)
            k_s = jnp.sum(k_i * S, axis=0, keepdims=True)   # k^T S [1, m dv]
            S = S + k_i * (vb_ref[0, 0, 1, i:i + 1]
                           * (vb_ref[0, 0, 0, i:i + 1] - k_s))
            s_out[0, 0, i] = S
            o_ref[0, 0, i:i + 1] = jnp.sum(cols(q, i) * S, axis=0,
                                           keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # no slot live: every turn maps to block 0, which goes back as it came
    @pl.when(meta_ref[1] == 0)
    def _():
        s_out[...] = s_ref[...]


@partial(jax.jit, static_argnames=("interpret",))
def _step_kernel_call(S_all, layer, q, k, v, g, beta, active, interpret):
    nl, b, hp, dk, w = S_all.shape
    h, dv = v.shape[1:]
    m = h // hp
    rb = _rows_per_block(hp, dk, w)
    nrb = hp // rb
    f32 = jnp.float32

    def heads_last(a):          # [B, H, c] -> [B, nrb, c, rb m]
        return jnp.swapaxes(a.astype(f32).reshape(b, nrb, rb * m, -1), 2, 3)

    # one decay a head is spread over the key channels here: Mosaic
    # broadcasts along sublanes or lanes, not both at once
    qkg = jnp.concatenate(
        [heads_last(a) for a in (q, k, jnp.broadcast_to(g, k.shape))], axis=2)
    vb = jnp.stack([v.astype(f32),
                    jnp.broadcast_to(beta.astype(f32)[..., None], v.shape)],
                   axis=1).reshape(b, 2, nrb, rb, w).swapaxes(1, 2)
    # the block each grid turn holds: its own where the slot is live, else
    # the last live turn's before it (the first live one's where none is)
    turn = jnp.arange(b * nrb, dtype=jnp.int32)
    on = jnp.repeat(active, nrb)
    before = lax.cummax(jnp.where(on, turn, -1), axis=0)
    blk = jnp.where(before >= 0, before, jnp.argmax(on).astype(jnp.int32))
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.any(active).astype(jnp.int32)])

    def state_map(bi, ri, meta, blk, act):
        at = blk[bi * nrb + ri]
        return (meta[0], at // nrb, at % nrb, 0, 0)

    def own(*tail):
        return lambda bi, ri, meta, blk, act: (bi, ri, *tail)

    state_spec = pl.BlockSpec((1, 1, rb, dk, w), state_map)
    S_all, o = pl.pallas_call(
        partial(_step_kernel, dk=dk, dv=dv, m=m, rb=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nrb),
            in_specs=[state_spec,
                      pl.BlockSpec((1, 1, 3 * dk, rb * m), own(0, 0)),
                      pl.BlockSpec((1, 1, 2, rb, w), own(0, 0, 0))],
            out_specs=[state_spec,
                       pl.BlockSpec((1, 1, rb, w), own(0, 0))]),
        out_shape=[jax.ShapeDtypeStruct(S_all.shape, f32),
                   jax.ShapeDtypeStruct((b, nrb, rb, w), f32)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_step_inplace",
    )(meta, blk, active.astype(jnp.int32), S_all, qkg, vb)
    return o.reshape(b, h, dv), S_all


def kda_step_inplace(S_all, layer, q, k, v, g, beta, active, impl: str = ""):
    """One token for every slot, over the state array AS THE ENGINE KEEPS
    IT: S_all [layers, B, H / m, dk, m dv] float32 (row b IS slot b; ``m``
    heads side by side along the lanes, ``pack_states``; m = 1 is the plain
    [layers, B, H, dk, dv]), of which this call moves layer ``layer`` (an
    int or a traced int32); q, k [B, H, dk]; v [B, H, dv]; g [B, H, dk] or
    [B, H, 1]; beta [B, H]; active [B] bool. Returns (o [B, H, dv], S_all):
    a slot that is not ``active`` keeps its state bit for bit (the kernel
    neither reads nor writes it) and its ``o`` is not specified (zeros from
    the kernel, the step of a stale state from the XLA body: no caller
    reads it). ``impl``: see ``step_impl``, which chooses when it is
    empty."""
    impl = impl or step_impl()
    if impl == "xla":
        m = q.shape[1] // S_all.shape[2]
        S = lax.dynamic_index_in_dim(S_all, layer, 0, keepdims=False)
        o, new_S = kda_step(unpack_states(S, m), q, k, v, g, beta)
        S = jnp.where(active[:, None, None, None], pack_states(new_S, m), S)
        return o, lax.dynamic_update_index_in_dim(S_all, S, layer, 0)
    return _step_kernel_call(S_all, layer, q, k, v, g, beta, active,
                             interpret=impl == "inplace_interpret")


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
