"""The device programs a ``ContinuousEngine`` dispatches, built once an
engine by ``build_programs``: admission (``_prefill``, ``_prefill_pages``,
``_prefill_suffix``), the slot-state installs (``_install``,
``_install_first``) and ONE ``_decode_chunk``. The loop that dispatches and
reads them is ``engine/continuous.py``; what a step computes is
``models/base.py``'s (a uniform spec) or the family module's (a per-layer
spec, ``models.base.layered_family``).

The decode chunk is one sequence whatever the cache: split the key, ``begin``,
``lax.scan`` of (``step`` -> ``unembed`` -> sample -> ``_advance``), ``end``,
pack. How a step REACHES its cache is a ``DecodeBody``, picked by the name
``continuous.resolve_decode_body`` resolved.
``dense``, ``window`` and ``hybrid`` freeze the page pools for a chunk and
write the chunk's fresh rows back once at its end: the per-step page scatter
they replace (``inline`` still makes it: a sliding-window prefix mask depends
on the growing length) held decode at ~28% of the dense engine's throughput
at 8B bs64.

The jitted functions' names are read outside the package (a device trace
sorts programs by "decode" / "prefill" in the module name): keep them.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.base import (
    ModelSpec,
    forward_decode,
    forward_decode_paged,
    forward_decode_window,
    forward_prefill_into_pages,
    forward_prefill_suffix,
    unembed,
    write_prefill_pages,
)
from ..ops.sampling import sample_tokens_with_logprobs


class DecodeBody(NamedTuple):
    """How the steps of one decode chunk reach the cache.

    ``begin(kp, vp, page_table, start_lengths, n_steps, n_ctx_pages)`` ->
    ``(frozen, cache)``: what every step of the chunk reads and none changes
    (the scan closes over it), and what the scan carries.

    ``step(params, last, lengths, start_lengths, frozen, cache, active)`` ->
    ``(hidden [B, D], cache, counters)``: one token for every slot;
    ``counters`` is an int32 vector as long as ``counters`` below, or None.

    ``end(frozen, cache, kp, vp, page_table, lengths, start_lengths)`` ->
    ``(kp, vp)``: the pools as the chunk leaves them, the chunk's one
    write-back made (``lengths - start_lengths`` rows a slot), or the pools
    the scan carried.

    ``counters``: a dotted name for each entry of a step's counter vector
    (None: an entry nothing reads); empty for a body whose steps count
    nothing, whose chunk then packs no counter rows."""

    begin: Callable
    step: Callable
    end: Callable
    counters: Tuple[Optional[str], ...] = ()


def _dense_body(spec: ModelSpec, page_size: int, max_seq_len: int
                ) -> DecodeBody:
    """XLA over a copy: the frozen prefix is gathered from the pages ONCE
    per chunk into a [L, B, Sb+W, Hkv, Dh] working buffer (Sb = a page
    bucket covering the longest live prefix) and the chunk runs the static
    engine's decode against it: one program per (n_steps, context-page
    bucket). The reference the kernel is pinned to."""
    L, Hkv, Dh = spec.n_layers, spec.n_kv_heads, spec.head_dim

    def begin(kp, vp, page_table, start_lengths, n_steps, n_ctx_pages):
        b = start_lengths.shape[0]
        s_ctx = n_ctx_pages * page_size
        pt = page_table[:, :n_ctx_pages]
        # one gather per chunk; the buffer stays in the cache dtype
        # (fp8 upcasts inside attention, fused into the read).
        # Chunk headroom is clamped at max_seq_len: no slot can
        # write past it (cap <= max_seq_len), and the whole buffer
        # is re-read EVERY step — un-clamped, a chunk starting at a
        # full context bucket would read s_ctx + n_steps wide when
        # s_ctx already covers every reachable position
        s_buf = min(s_ctx + n_steps, max(max_seq_len, s_ctx))
        with jax.named_scope("attn.kv_gather"):
            ctx_k = kp[:, pt].reshape(L, b, s_ctx, Hkv, Dh)
            ctx_v = vp[:, pt].reshape(L, b, s_ctx, Hkv, Dh)
            zpad = jnp.zeros((L, b, s_buf - s_ctx, Hkv, Dh), ctx_k.dtype)
            ctx_k = jnp.concatenate([ctx_k, zpad], axis=2)
            ctx_v = jnp.concatenate([ctx_v, zpad], axis=2)
        return n_steps, (ctx_k, ctx_v)

    def step(params, last, lengths, start_lengths, n_steps, cache, active):
        # dense in-place decode (models.base.forward_decode): slots whose
        # start prefix is shorter than Sb overwrite their own gathered
        # garbage; attention masks by length. Retired slots keep scattering
        # at their stale length into their OWN row (clamped in-bounds) —
        # discarded by the zero writeback count of ``end``.
        hidden, ctx_k, ctx_v = forward_decode(spec, params, last, lengths,
                                              *cache)
        return hidden, (ctx_k, ctx_v), None

    def end(n_steps, cache, kp, vp, page_table, lengths, start_lengths):
        # each slot's fresh KV sits at [start, start + produced-this-chunk)
        # in its dense row; the count mask drops everything past it
        ctx_k, ctx_v = cache
        with jax.named_scope("chunk.end"):
            bi = jnp.arange(lengths.shape[0])[:, None]
            idx = start_lengths[:, None] + jnp.arange(n_steps)[None, :]
        with jax.named_scope("attn.kv_update"):
            return write_prefill_pages(
                kp, vp, ctx_k[:, bi, idx], ctx_v[:, bi, idx], page_table,
                lengths - start_lengths, start=start_lengths)

    return DecodeBody(begin, step, end)


def _window_body(spec: ModelSpec, interpret: bool) -> DecodeBody:
    """The kernel's operand is the page pool itself; fresh K/V collects in a
    side window. No dense copy, no per-layer slice, and the program does not
    depend on the context's page bucket (n_ctx_pages stays 0: one program
    per n_steps)."""
    fwd_window = partial(forward_decode_window, interpret=interpret)

    def begin(kp, vp, page_table, start_lengths, n_steps, n_ctx_pages):
        with jax.named_scope("chunk.begin"):
            side_k = jnp.zeros(
                (spec.n_layers, start_lengths.shape[0], n_steps,
                 spec.n_kv_heads, spec.head_dim), spec.jnp_dtype)
            side_v = jnp.zeros_like(side_k)
        return page_table, (kp, vp, side_k, side_v)

    def step(params, last, lengths, start_lengths, page_table, cache,
             active):
        kp, vp, side_k, side_v = cache
        hidden, side_k, side_v = fwd_window(
            spec, params, last, lengths, start_lengths, kp, vp, page_table,
            side_k, side_v, active)
        return hidden, (kp, vp, side_k, side_v), None

    def end(_frozen, cache, _kp, _vp, page_table, lengths, start_lengths):
        # one batched scatter merges the chunk's fresh KV into the pages
        # (0.03 ms at 8B bs64 — vs ~45 ms/step for per-step writes);
        # inactive-slot garbage past each slot's produced count is dropped
        # by the length mask
        kp, vp, side_k, side_v = cache
        with jax.named_scope("attn.kv_update"):
            return write_prefill_pages(
                kp, vp, side_k, side_v, page_table, lengths - start_lengths,
                start=start_lengths)

    return DecodeBody(begin, step, end)


def _inline_body(spec: ModelSpec) -> DecodeBody:
    """Fresh K/V is scattered into the pages every step."""

    def begin(kp, vp, page_table, start_lengths, n_steps, n_ctx_pages):
        return page_table, (kp, vp)

    def step(params, last, lengths, start_lengths, page_table, cache,
             active):
        hidden, kp, vp = forward_decode_paged(
            spec, params, last, lengths, *cache, page_table, active)
        return hidden, (kp, vp), None

    def end(_frozen, cache, _kp, _vp, page_table, lengths, start_lengths):
        return cache            # every step wrote its own row

    return DecodeBody(begin, step, end)


def _family_body(spec: ModelSpec, fam, attn_impl: str) -> DecodeBody:
    """A per-layer spec's body, over its family module: ``kp`` is the
    family's paged rows, read where they lie (``decode_context``), the
    chunk's own rows gather in a side window the family writes back once
    (``write_side``); ``vp`` is its per-slot state (``engine/paged_kv.py``),
    which rides the scan and moves only for rows ``active`` at that step."""

    def begin(kp, vp, page_table, start_lengths, n_steps, n_ctx_pages):
        ctx = fam.decode_context(kp, page_table, attn_impl)   # live pages
        # a row a step of every layer that keeps K|V or latent rows, the
        # window layers' (a pool of their own, in the state) first; with an
        # indexer the token's index key rides in the row's last lanes
        with jax.named_scope("chunk.begin"):
            side = jnp.zeros((spec.window_layers + spec.paged_layers,
                              start_lengths.shape[0], n_steps,
                              kp.shape[-1] + spec.index_head_dim), kp.dtype)
        return ctx, (side, vp)

    def step(params, last, lengths, start_lengths, ctx, cache, active):
        hidden, side, state, counters = fam.forward_decode_step(
            spec, params, last, lengths, start_lengths, ctx, *cache, active)
        return hidden, (side, state), counters

    def end(_ctx, cache, kp, _vp, page_table, lengths, start_lengths):
        side, state = cache
        with jax.named_scope("chunk.end"):
            counts = lengths - start_lengths
        return fam.write_side(kp, state, side, page_table, counts,
                              start_lengths)

    return DecodeBody(begin, step, end, tuple(fam.DECODE_COUNTERS))


def build_programs(spec: ModelSpec, body: str, attn_impl: str, fam,
                   fwd_prefill, page_size: int, max_seq_len: int
                   ) -> Tuple[Any, ...]:
    """One engine's jitted programs ``(_prefill, _prefill_pages,
    _prefill_suffix, _decode_chunk, _install, _install_first)``, closed
    over its constants: ``body`` / ``attn_impl`` as
    ``continuous.resolve_decode_body`` resolved them, ``fam`` the family
    module of a per-layer spec (None for a uniform one), ``fwd_prefill``
    what ``prefill_fn_for`` chose."""
    body = {"hybrid": lambda: _family_body(spec, fam, attn_impl),
            "dense": lambda: _dense_body(spec, page_size, max_seq_len),
            "window": lambda: _window_body(
                spec, attn_impl.endswith("_interpret")),
            "inline": lambda: _inline_body(spec)}[body]()

    def _sample_firsts(params, hidden, seq_lens, sampling, key):
        """Shared prefill tail: last-token logits → sampled first
        token + logprob, packed into ONE [2, B] int32 buffer (what
        ``_install_first`` and ``_read_firsts`` take — change it here
        and BOTH admission programs stay in sync). Sampling happens
        in-program because eager sampling is a chain of separate
        dispatches whose launch latencies all land in TTFT."""
        with jax.named_scope("head.firsts"):
            last = hidden[jnp.arange(hidden.shape[0]), seq_lens - 1]
        logits = unembed(spec, params, last)
        first, lp = sample_tokens_with_logprobs(logits, sampling, key)
        with jax.named_scope("head.firsts"):
            return jnp.stack(
                [first, jax.lax.bitcast_convert_type(lp, jnp.int32)])

    @jax.jit
    def _prefill(params, tokens, seq_lens, sampling, key):
        hidden, ks, vs = fwd_prefill(spec, params, tokens, seq_lens)
        return (_sample_firsts(params, hidden, seq_lens, sampling, key),
                ks, vs)

    @partial(jax.jit, donate_argnums=(3, 4))
    def _prefill_pages(params, tokens, seq_lens, kp, vp, table_rows,
                       sampling, key, slot_ids=None):
        """Fused admission prefill: per-layer KV scatters straight
        into the (donated) pools inside the layer scan — no
        [L, bb, T, Hkv, Dh] transient (~2.1 GB at 8B bb=128, the
        nondeterministic bs128-warmup OOM) and one dispatch instead
        of prefill + page-write. A per-layer spec's: whole prompts at a
        padded bucket, the paged rows into the pages, each row's state as of
        its TRUE end into its slot (``slot_ids``), and the family's prefill
        counters (None for a uniform spec)."""
        if fam is None:
            hidden, kp, vp = forward_prefill_into_pages(
                spec, params, tokens, seq_lens, kp, vp, table_rows)
            counters = None
        else:
            hidden, kp, vp, counters = fam.forward_prefill_into_pages(
                spec, params, tokens, seq_lens, kp, vp, table_rows, slot_ids)
        return (_sample_firsts(params, hidden, seq_lens, sampling, key),
                kp, vp, counters)

    @partial(jax.jit, static_argnames=("n_ctx_pages",))
    def _prefill_suffix(params, tokens, suffix_lens, n_ctx, phys_pages,
                        k_pages, v_pages, sampling, key,
                        n_ctx_pages: int):
        """Continue partially prefilled sequences: prefill only each
        row's suffix, attending over its context gathered from its
        pages (``phys_pages`` [B, n_ctx_pages]). Batched — one program
        per (batch bucket, suffix bucket, ctx-pages bucket) — shared by
        prefix-cache hits and the parallel chunked-prefill advance.
        Rows whose true context is shorter than the page bucket are
        masked by ``n_ctx`` inside suffix attention."""
        L = spec.n_layers
        Hkv, Dh = spec.n_kv_heads, spec.head_dim
        b = tokens.shape[0]
        tc = n_ctx_pages * page_size
        ck = k_pages[:, phys_pages].reshape(L, b, tc, Hkv, Dh)
        cv = v_pages[:, phys_pages].reshape(L, b, tc, Hkv, Dh)
        ck = ck.astype(spec.jnp_dtype)
        cv = cv.astype(spec.jnp_dtype)
        hidden, ks, vs = forward_prefill_suffix(
            spec, params, tokens, suffix_lens, n_ctx, ck, cv
        )
        return (_sample_firsts(params, hidden, suffix_lens, sampling, key),
                ks, vs)

    def _advance(next_tok, lp, lengths, last, active, produced, *,
                 cap, max_new, eos_ids, stop_mat, use_stops):
        """Shared post-sample bookkeeping of one decode step."""
        was_active = active
        produced = produced + was_active.astype(jnp.int32)
        hit_eos = (next_tok == eos_ids) & (eos_ids >= 0)
        new_len = lengths + was_active.astype(jnp.int32)
        done = (hit_eos | (produced >= max_new)
                | (new_len >= cap))
        if use_stops:
            # device-side single-token stops ([B, K] stop-id
            # matrix): a stopped slot goes inactive IN-CHUNK
            # instead of decoding dead tokens until the host scan
            # sees it. Static flag: engines with no live stop ids
            # keep compiling the stop-free program.
            done = done | ((next_tok[:, None] == stop_mat)
                           & (stop_mat >= 0)).any(axis=-1)
        active = was_active & ~done
        last = jnp.where(was_active, next_tok, last)
        emitted = jnp.where(was_active, next_tok, -1)
        lp = jnp.where(was_active, lp, 0.0)
        return new_len, last, active, produced, emitted, lp

    @partial(jax.jit,
             static_argnames=("n_steps", "n_ctx_pages", "use_stops"),
             donate_argnums=(1, 2, 3, 4, 5, 6))
    def _decode_chunk(
        params, kp, vp, lengths, last_tokens, active, produced,
        page_table, cap, max_new, sampling, eos_ids, stop_mat, key,
        n_steps: int, n_ctx_pages: int = 0, use_stops: bool = False,
    ):
        """``n_steps`` tokens for every live slot, through ``body``."""
        start_lengths = lengths
        advance = partial(_advance, cap=cap, max_new=max_new,
                          eos_ids=eos_ids, stop_mat=stop_mat,
                          use_stops=use_stops)
        with jax.named_scope("chunk.begin"):
            keys = jax.random.split(key, n_steps)
        frozen, cache = body.begin(kp, vp, page_table, start_lengths,
                                   n_steps, n_ctx_pages)
        with jax.named_scope("chunk.begin"):
            counters = (jnp.zeros((len(body.counters),), jnp.int32)
                        if body.counters else None)

        def step(carry, step_key):
            cache, lengths, last, active, produced, counters = carry
            hidden, cache, counted = body.step(
                params, last, lengths, start_lengths, frozen, cache, active)
            logits = unembed(spec, params, hidden)
            next_tok, lp = sample_tokens_with_logprobs(
                logits, sampling, step_key)
            with jax.named_scope("chunk.advance"):
                new_len, last, active, produced, emitted, lp = advance(
                    next_tok, lp, lengths, last, active, produced)
                if body.counters:
                    counters = counters + counted
            return ((cache, new_len, last, active, produced, counters),
                    (emitted, lp))

        carry, (toks, lps) = jax.lax.scan(
            step, (cache, lengths, last_tokens, active, produced, counters),
            keys)
        cache, lengths, last, active, produced, counters = carry
        kp, vp = body.end(frozen, cache, kp, vp, page_table, lengths,
                          start_lengths)
        # pack tokens + logprobs (bitcast) + active flags + lengths (+ the
        # body's counters for the chunk, a row each) into ONE output
        # buffer: the host makes exactly one blocking read per chunk (each
        # sync is a full round trip on remote devices)
        with jax.named_scope("chunk.pack"):
            rows = [toks, jax.lax.bitcast_convert_type(lps, jnp.int32),
                    active[None].astype(jnp.int32), lengths[None]]
            if body.counters:
                rows.append(jnp.broadcast_to(
                    counters[:, None],
                    (counters.shape[0], lengths.shape[0])))
            packed = jnp.concatenate(rows, axis=0)
        return (kp, vp, lengths, last, active, produced), packed

    def _set_slots(state, slots, vals, first, live):
        """The per-slot state of ``slots`` as an admission leaves it.
        ``state``: lengths, last, active, produced, max_new, eos, temps,
        top_k, top_p, min_p, stops; ``slots`` is a padded int32 vector whose
        pad entries hold ``max_slots`` and fall out of range
        (``mode="drop"``)."""
        values = (vals["prompt_len"], first, live, 1, vals["max_new"],
                  vals["eos"], vals["temp"], vals["top_k"], vals["top_p"],
                  vals["min_p"], vals["stops"])
        with jax.named_scope("slots.install"):
            return tuple(a.at[slots].set(v, mode="drop")
                         for a, v in zip(state, values))

    @partial(jax.jit, donate_argnums=tuple(range(11)))
    def _install(*args):
        """``(*state, slots, vals)``: all per-slot state writes of a WHOLE
        admission round in ONE dispatch (an eager .at[].set chain is one
        dispatch per write), the first tokens from the host
        (``vals["first"]``)."""
        *state, slots, vals = args
        return _set_slots(state, slots, vals, vals["first"], True)

    @partial(jax.jit, donate_argnums=tuple(range(11)))
    def _install_first(*args):
        """``(*state, slots, vals, first_dev, cols)``: the install of a
        local prefill's rows, like ``_install`` but the first tokens stay
        ON DEVICE — ``first_dev`` is the prefill program's [2, bb] output,
        ``cols`` maps each row to its column in it. The tokens seed the
        decode state directly; the host reads them from ``first_dev`` after
        the next decode dispatch."""
        *state, slots, vals, first_dev, cols = args
        with jax.named_scope("slots.install"):
            first = first_dev[0, cols]
            # a prefill-sampled first token that IS eos must not decode:
            # the device sees it first, so the slot comes up inactive (the
            # host retires it when it reads the token)
            live = (first != vals["eos"]) | (vals["eos"] < 0)
        return _set_slots(state, slots, vals, first, live)

    return (_prefill, _prefill_pages, _prefill_suffix, _decode_chunk,
            _install, _install_first)
