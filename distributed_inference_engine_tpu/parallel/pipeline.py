"""Pipeline parallelism: GPipe-style microbatched stages over the ``pp``
mesh axis.

The last parallelism strategy SURVEY.md §2.3 reserves ("stage-sharded mesh
axis + microbatched decode"): the stacked ``[n_layers, ...]`` parameter
layout (models/base.py) splits naturally — stage ``s`` of ``S`` holds layers
``[s·L/S, (s+1)·L/S)`` as its local shard of every block tensor, placed with
``P("pp", ...)`` on the leading axis.

TPU-native execution model: one ``shard_map`` over the ``pp`` axis runs the
classic pipeline schedule as an SPMD program —

- each tick, every stage applies its local layer stack (``lax.scan``) to the
  activation it currently holds, then the activations rotate one stage
  forward with ``lax.ppermute`` over ICI;
- stage 0 injects microbatch ``t`` at tick ``t``; the last stage holds the
  finished microbatch ``t`` at tick ``t + S - 1``; a run of
  ``n_micro + S - 1`` ticks drains the pipeline (the S-1 bubble ticks are
  the standard GPipe cost, amortized by more microbatches);
- per-microbatch ``seq_lens`` travel WITH the activations through the
  rotation (each stage is processing a different microbatch at any tick, so
  the attention mask data must ride the pipe, not be indexed by tick);
- embedding runs before the pipe and the LM head after it (both replicated
  over ``pp``); the batch dim shards over ``dp`` as usual, so dp×pp compose.

Everything is differentiable (``ppermute`` has a transpose rule), so the
same schedule backs the pipeline training step.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.base import (
    ModelSpec,
    Params,
    embed,
    init_params,
    next_token_xent,
    transformer_block,
    unembed,
)
from ..ops.attention import causal_attention


def pp_param_pspecs(spec: ModelSpec) -> Any:
    """PartitionSpec tree for pipeline placement: every block tensor's
    leading (layer) axis shards over ``pp``; embeddings, final norm, and LM
    head are replicated (they run outside the pipe)."""
    from .sharding import param_pspecs

    base = dict(param_pspecs(spec))
    # replace each block pspec's leading (layer) axis with pp; trailing tp
    # dims from param_pspecs compose untouched
    base["blocks"] = {k: P("pp", *tuple(v)[1:])
                      for k, v in base["blocks"].items()}
    return base


def _stage_body(spec: ModelSpec, blocks: Params, x: jnp.ndarray,
                seq_lens: jnp.ndarray) -> jnp.ndarray:
    """Apply this stage's local layer stack to activations ``x``
    ([mb, T, D]) — ``models.base.transformer_block`` with the dense causal
    attention, KV discarded (training/scoring path)."""
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

    def attn(q, k, v):
        return causal_attention(q, k, v, seq_lens,
                                window=spec.sliding_window)

    def body(x, blk):
        x, _, _, _ = transformer_block(spec, blk, x, positions, attn)
        return x, None

    x, _ = lax.scan(body, x, blocks)
    return x


def pipeline_hidden(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,     # [B, T] (B = n_micro * microbatch)
    seq_lens: jnp.ndarray,   # [B]
    mesh: Mesh,
    n_micro: int,
) -> jnp.ndarray:
    """Run the layer stack as a pp-staged pipeline; returns final hidden
    states [B, T, D] (pre final-norm), numerically identical to the dense
    forward."""
    n_stages = mesh.shape["pp"]
    b, t = tokens.shape
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    if spec.n_layers % n_stages:
        raise ValueError(
            f"n_layers {spec.n_layers} not divisible by pp stages "
            f"{n_stages} — each stage needs an equal slice of the layer "
            f"stack")
    if spec.n_experts:
        # the stage body would silently use the drop-free inference MoE
        # path and discard the router load-balance aux loss — training an
        # MoE through the pipe without the penalty invites router collapse,
        # so refuse until aux plumbing rides the schedule
        raise ValueError(
            "pipeline parallelism does not yet support MoE specs "
            "(router aux loss is not plumbed through the pipe; use "
            "parallel.train.make_train_step with the ep axis)")
    mb = b // n_micro

    x = embed(spec, params, tokens,
              jnp.broadcast_to(jnp.arange(t)[None, :], (b, t)))
    xs = x.reshape(n_micro, mb, t, -1)
    lens = seq_lens.reshape(n_micro, mb)

    blocks_spec = jax.tree.map(lambda _: P("pp"), params["blocks"])

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(blocks_spec, P(None, "dp"), P(None, "dp")),
        out_specs=P(None, "dp"),
        check_vma=False,
    )
    def run(blocks, xs, lens):
        stage = lax.axis_index("pp")
        steps = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        state = jnp.zeros_like(xs[0])
        state_lens = jnp.zeros_like(lens[0])
        out = jnp.zeros_like(xs)

        def tick(carry, ti):
            state, state_lens, out = carry
            # stage 0 ingests microbatch ti (a clipped gather; ticks past
            # the last microbatch feed the bubble and are never read back)
            inj = lax.dynamic_index_in_dim(
                xs, jnp.clip(ti, 0, n_micro - 1), axis=0, keepdims=False)
            inj_lens = lax.dynamic_index_in_dim(
                lens, jnp.clip(ti, 0, n_micro - 1), axis=0, keepdims=False)
            state = jnp.where(stage == 0, inj, state)
            state_lens = jnp.where(stage == 0, inj_lens, state_lens)

            state = _stage_body(spec, blocks, state, state_lens)

            # last stage completed microbatch ti-(S-1); write it home
            widx = ti - (n_stages - 1)
            write = (stage == n_stages - 1) & (widx >= 0)
            out = lax.dynamic_update_index_in_dim(
                out,
                jnp.where(write,
                          state,
                          lax.dynamic_index_in_dim(
                              out, jnp.clip(widx, 0, n_micro - 1),
                              axis=0, keepdims=False)),
                jnp.clip(widx, 0, n_micro - 1), axis=0)

            # rotate activations one stage forward over ICI
            state = lax.ppermute(state, "pp", perm)
            state_lens = lax.ppermute(state_lens, "pp", perm)
            return (state, state_lens, out), None

        (state, state_lens, out), _ = lax.scan(
            tick, (state, state_lens, out), jnp.arange(steps))
        # results live on the last stage only; broadcast over pp so the
        # out_spec (replicated over pp) is truthful
        out = lax.psum(jnp.where(stage == n_stages - 1, out,
                                 jnp.zeros_like(out)), "pp")
        return out

    hidden = run(params["blocks"], xs, lens)
    return hidden.reshape(b, t, -1)


def pipeline_forward_train(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,
    seq_lens: jnp.ndarray,
    mesh: Mesh,
    n_micro: int,
) -> jnp.ndarray:
    """Full-sequence logits [B, T, V] fp32 through the pipeline."""
    hidden = pipeline_hidden(spec, params, tokens, seq_lens, mesh, n_micro)
    return unembed(spec, params, hidden)


def pipeline_lm_loss(
    spec: ModelSpec,
    params: Params,
    tokens: jnp.ndarray,
    seq_lens: jnp.ndarray,
    mesh: Mesh,
    n_micro: int,
) -> jnp.ndarray:
    logits = pipeline_forward_train(spec, params, tokens, seq_lens, mesh,
                                    n_micro)
    return next_token_xent(logits, tokens, seq_lens)


def make_pp_train_step(
    spec: ModelSpec,
    mesh: Mesh,
    n_micro: int,
    learning_rate: float = 1e-3,
):
    """(init_state, train_step) with parameters stage-sharded over ``pp``
    and the batch over ``dp`` — the pipeline twin of
    ``parallel.train.make_train_step``.

    ``ppermute`` differentiates, so one ``value_and_grad`` over the
    pipelined loss gives the full backward schedule; optimizer state
    inherits the parameters' stage sharding (adamw moments live with their
    stage's weights)."""
    import optax

    tx = optax.adamw(learning_rate)
    pspecs = pp_param_pspecs(spec)
    param_shardings = jax.tree.map(
        lambda p: NamedSharding(mesh, p), pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
    batch_sharding = NamedSharding(mesh, P("dp"))
    repl = NamedSharding(mesh, P())

    def init_state(key: jax.Array):
        params = init_params(spec, key)
        params = jax.tree.map(jax.device_put, params, param_shardings)
        opt_state = tx.init(params)
        return params, opt_state

    def step(state, tokens, seq_lens):
        params, opt_state = state
        loss, grads = jax.value_and_grad(
            lambda p: pipeline_lm_loss(spec, p, tokens, seq_lens, mesh,
                                       n_micro)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), loss

    train_step = jax.jit(
        step,
        in_shardings=(None, batch_sharding, batch_sharding),
        out_shardings=(None, repl),
        donate_argnums=(0,),
    )
    return init_state, train_step
