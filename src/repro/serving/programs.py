"""Jit-program layer of the serving engine.

This module is the device side of the serving stack: every jit-compiled
computation the scheduler (serving/scheduler.py) dispatches lives here, with
no request/queue/session bookkeeping mixed in —

* bucketed **prefill** (one compile per prompt-length bucket, in-jit per-slot
  cache splice),
* **extend** continuations (dense chunked prefill and paged suffix prefill
  through block tables),
* the chunked **decode** loop (``lax.while_loop``, per-slot done mask,
  on-device per-slot sampling with per-request key chains),
* the fused speculative **verify** step (forward + accept + accept-length
  state rewind in ONE jit),
* snapshot-arena **capture/restore** splices (per-prefix recurrent-state
  sharing).

The scheduler owns the mutable state (cache, params, slots, counters) and
passes it through; ``EnginePrograms`` owns the model and the compiled
callables. Splitting the layers keeps the step loop readable and lets the
program set be reused by any frontend (``repro.serving.server.LLMServer``,
the deprecated ``ServingEngine`` shim, future batch runners) without
re-tracing.
"""
from __future__ import annotations

import contextlib
import random
import time
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import use_rules
from repro.serving.faults import DeadLetterError, RetryPolicy, TransientFault
from repro.serving.sampler import accept_batched, sample_batched


def slot_extract(cache, slot):
    """Single-row view of slot ``slot``: scan leaves are [L, B, ...], tail
    leaves [B, ...] (mirrors ``slot_splice``)."""
    def _scan_get(full):
        return jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=1)

    def _tail_get(full):
        return jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=0)

    return {k: jax.tree.map(_scan_get if k == "scan" else _tail_get, cache[k])
            for k in cache}


def slot_splice(cache, cache1, slot):
    """Write a single-row cache pytree back into row ``slot``."""
    def _scan_leaf(full, one):
        return jax.lax.dynamic_update_slice(
            full, one.astype(full.dtype),
            (jnp.int32(0), slot) + (jnp.int32(0),) * (full.ndim - 2))

    def _tail_leaf(full, one):
        return jax.lax.dynamic_update_slice(
            full, one.astype(full.dtype),
            (slot,) + (jnp.int32(0),) * (full.ndim - 1))

    return {k: jax.tree.map(_scan_leaf if k == "scan" else _tail_leaf,
                            cache[k], cache1[k])
            for k in cache}


def select_rows(new_cache, old_cache, keep):
    """Per-row cache select: rows with ``keep`` take the new cache, the rest
    keep the old one bit-exactly. Scan leaves are [L, B, ...], tail leaves
    [B, ...] (the ``slot_extract`` convention)."""
    def _scan_sel(n, o):
        return jnp.where(keep.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o)

    def _tail_sel(n, o):
        return jnp.where(keep.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)

    return {k: jax.tree.map(_scan_sel if k == "scan" else _tail_sel,
                            new_cache[k], old_cache[k])
            for k in new_cache}


def auto_buckets(capacity: int, lo: int = 32) -> Tuple[int, ...]:
    """Power-of-two prompt-length buckets up to (and including) capacity."""
    buckets = []
    b = min(lo, capacity)
    while b < capacity:
        buckets.append(b)
        b *= 2
    buckets.append(capacity)
    return tuple(buckets)


class EnginePrograms:
    """The compiled program set for one (model config, engine config) pair.

    Stateless apart from the model/params-independent compile caches: the
    scheduler threads cache/params in and out of every call. ``keys``/
    ``counts`` in the decode loop implement per-request RNG chains — row
    ``b`` samples its ``t``-th token with ``fold_in(keys[b], counts[b])``,
    so a request's stochastic output is a function of its own seed and
    position only, never of batch composition (see SamplingParams.seed).
    """

    def __init__(self, model, cfg, engine_cfg, *, capacity: int,
                 num_slots: int, eos_id: int, freeze_done_rows: bool,
                 snapshots: bool, spec: bool,
                 injector=None, retry: RetryPolicy = None,
                 watchdog_s: float = None, rules=None):
        self.model = model
        self.cfg = cfg
        self.engine_cfg = engine_cfg
        self.capacity = capacity
        self.num_slots = num_slots
        self.eos_id = eos_id
        self.freeze_done_rows = freeze_done_rows
        # sharding rule set (distributed/sharding.py "serve" phase) or None.
        # Dispatches run under use_rules(rules), so the model's constrain()
        # calls resolve to NamedShardings at trace time and the jits
        # partition over the mesh; None (single device) traces no
        # constraints at all — the pre-mesh programs, byte-for-byte.
        self.rules = rules
        # fault layer: every public dispatch goes through _run (injector
        # hook + bounded retry of TransientFaults + watchdog accounting)
        self.injector = injector
        self.retry = retry or RetryPolicy()
        self.watchdog_s = watchdog_s
        self.dispatch_retries = 0       # TransientFaults retried
        self.watchdog_stalls = 0        # dispatches slower than watchdog_s
        self._retry_rng = random.Random(0)   # backoff jitter (deterministic)

        # every program donates the cache (or snapshot arena) it rewrites, so
        # XLA updates it in place; the scheduler rebinds the result and never
        # touches the donated buffers again
        dargs = (1,)
        self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=dargs)
        self._decode_chunk_jit = jax.jit(self._decode_chunk_fn,
                                         donate_argnums=dargs)
        self._extend_jit = jax.jit(self._extend_fn, donate_argnums=dargs,
                                   static_argnames=("sample",))
        self._extend_paged_jit = jax.jit(self._extend_paged_fn,
                                         donate_argnums=dargs,
                                         static_argnames=("sample",))
        if snapshots:
            self._snap_capture_jit = jax.jit(self._snap_capture_fn,
                                             donate_argnums=(0,))
            self._snap_restore_jit = jax.jit(self._snap_restore_fn,
                                             donate_argnums=(0,))
        if spec:
            # ONE jit per verify step for every arch: forward + accept +
            # accept-length state rewind (model.verify_commit) fused
            self._verify_jit = jax.jit(self._verify_fn, donate_argnums=dargs)

    # ---- guarded dispatch --------------------------------------------------
    def _run(self, site: str, fn, *args, **kwargs):
        """One guarded device dispatch: the fault-injector hook fires first
        (it may stall — counted against the watchdog — or raise), then the
        jit call. ``TransientFault``s retry with exponential backoff +
        jitter up to ``retry.max_attempts``, then dead-letter; anything else
        propagates untouched for the scheduler's isolation paths (a real jit
        exception is never retried — with donation on, the inputs may
        already be consumed)."""
        attempt = 0
        while True:
            t0 = time.perf_counter()
            try:
                if self.injector is not None:
                    self.injector.check(site)
                # context managers are single-use: build one per attempt
                with (use_rules(self.rules) if self.rules is not None
                      else contextlib.nullcontext()):
                    out = fn(*args, **kwargs)
            except TransientFault as e:
                attempt += 1
                self.dispatch_retries += 1
                if attempt >= self.retry.max_attempts:
                    raise DeadLetterError(
                        f"{site}: {self.retry.max_attempts} attempts "
                        "exhausted") from e
                time.sleep(self.retry.delay(attempt, self._retry_rng))
                continue
            if (self.watchdog_s is not None
                    and time.perf_counter() - t0 > self.watchdog_s):
                self.watchdog_stalls += 1
            return out

    def prefill(self, *args):
        return self._run("prefill", self._prefill_jit, *args)

    def extend(self, *args, sample: bool):
        return self._run("extend", self._extend_jit, *args, sample=sample)

    def extend_paged(self, *args, sample: bool):
        return self._run("extend_paged", self._extend_paged_jit, *args,
                         sample=sample)

    def decode_chunk(self, *args):
        return self._run("decode", self._decode_chunk_jit, *args)

    def verify(self, *args):
        return self._run("verify", self._verify_jit, *args)

    def snap_capture(self, *args):
        return self._run("snap_capture", self._snap_capture_jit, *args)

    def snap_restore(self, *args):
        return self._run("snap_restore", self._snap_restore_jit, *args)

    # ---- prefill / extend --------------------------------------------------
    def _prefill_fn(self, params, cache, tokens, positions, slot, length, key,
                    temperature, top_k):
        """Prefill one (padded) prompt and splice it into the shared cache.

        Everything — forward pass, per-slot cache splice, first-token sample —
        happens in one jit, compiled once per bucket length.
        """
        cache1 = self.model.init_cache(1, self.capacity)
        batch = {("frames" if self.cfg.modality == "audio_frames" else "tokens"): tokens,
                 "positions": positions}
        logits, cache1 = self.model.prefill(params, batch, cache1,
                                            length=length, with_logits="last")
        tok = self._sample_last(logits, length, key, temperature, top_k)
        # splice the single-row cache into slot `slot` of the shared cache;
        # scan caches are [L, B, ...] (batch dim 1), tail caches [B, ...]
        return slot_splice(cache, cache1, slot), tok

    def _sample_last(self, logits, length, key, temperature, top_k):
        """Sample one token from the logits at position ``length - 1``
        (or from already-sliced ``with_logits="last"`` logits [B, 1, V])."""
        if logits.shape[1] == 1:
            last = logits[:, 0]                                      # [1, V]
        else:
            last = jax.lax.dynamic_index_in_dim(logits, length - 1, axis=1,
                                                keepdims=False)      # [1, V]
        tok = sample_batched(last, key, temperature=temperature[None],
                             top_k=top_k[None], vocab_limit=self.cfg.vocab_size)
        return tok[0]

    def _extend_fn(self, params, cache, tokens, positions, slot, start,
                   length, key, temperature, top_k, *, sample: bool):
        """Dense chunked-prefill continuation for one slot.

        Extract the slot's cache row, run ``model.extend`` (the chunk attends
        to the already-prefilled prefix + itself; recurrent state resumes),
        splice the row back — all in one jit, compiled once per chunk shape.
        ``sample=True`` (the prompt's final chunk) additionally unembeds and
        samples at the last valid position; intermediate chunks skip the
        unembed matmul entirely.
        """
        cache1 = slot_extract(cache, slot)
        tok_key = ("frames" if self.cfg.modality == "audio_frames" else "tokens")
        batch = {tok_key: tokens, "positions": positions}
        logits, cache1 = self.model.extend(
            params, batch, cache1, start, length=length,
            with_logits="last" if sample else False)
        tok = (self._sample_last(logits, length, key, temperature, top_k)
               if sample else jnp.int32(-1))
        return slot_splice(cache, cache1, slot), tok

    def _extend_paged_fn(self, params, pool, tokens, positions, bt, start,
                         length, key, temperature, top_k, *, sample: bool):
        """Paged prefill: write the chunk's K/V into this request's pages and
        attend to the full block-table view (shared prefix pages included —
        the radix-matched prefix is never recomputed)."""
        tok_key = ("frames" if self.cfg.modality == "audio_frames" else "tokens")
        batch = {tok_key: tokens, "positions": positions}
        logits, pool = self.model.extend(
            params, batch, pool, start, length=length, block_tables=bt,
            with_logits="last" if sample else False)
        tok = (self._sample_last(logits, length, key, temperature, top_k)
               if sample else jnp.int32(-1))
        return pool, tok

    # ---- chunked decode ----------------------------------------------------
    def _decode_chunk_fn(self, params, cache, last_tok, cache_lens, remaining,
                         done, temps, top_ks, keys, prompt_lens,
                         block_tables=None):
        """Decode up to ``decode_chunk`` tokens for every live slot on device.

        Per-slot done mask (EOS / budget / capacity); finished or empty slots
        keep running in the fixed batch but stop emitting and stop advancing
        their cache row. ``keys`` [B, 2] are per-request PRNG keys and
        ``prompt_lens`` [B] each row's prompt length — the number of tokens
        row ``b`` has sampled so far is then ``cache_lens[b] -
        prompt_lens[b] + 1`` (derived in-jit, no extra host transfer or
        loop carry), and its next token uses ``fold_in(keys[b], count)``:
        stochastic outputs are reproducible per request regardless of which
        other requests share the batch. Statically greedy batches
        (``temps is None``) trace no RNG at all. Returns everything the host
        needs in one pull.
        """
        chunk = self.engine_cfg.decode_chunk
        B = self.num_slots
        eos = self.eos_id
        tok_buf = jnp.zeros((chunk, B), jnp.int32)
        emit_buf = jnp.zeros((chunk, B), bool)

        def cond(st):
            i = st[0]
            return (i < chunk) & jnp.any(~st[5])

        def body(st):
            i, cache, last, clens, rem, done, tb, eb = st
            if self.cfg.modality == "audio_frames":
                # same frame-embedding stub the admission path applies
                toks = jax.nn.one_hot(last[:, None] % self.cfg.d_model,
                                      self.cfg.d_model,
                                      dtype=jnp.dtype(self.cfg.dtype))
                batch = {"frames": toks, "positions": clens[:, None]}
            else:
                batch = {"tokens": last[:, None], "positions": clens[:, None]}
            logits, new_cache = self.model.decode_step(params, batch, cache,
                                                       clens,
                                                       block_tables=block_tables)
            if self.freeze_done_rows:
                # stateful archs: a done-masked row must not keep advancing
                # its recurrent / conv / mLSTM / sLSTM state on a stale
                # input — above all a spec-handled slot sitting this chunk
                # out, which continues decoding next step. Full-attention
                # rows skip this (their stale write is position-masked and
                # idempotent; their caches are also the big ones).
                cache = select_rows(new_cache, cache, ~done)
            else:
                cache = new_cache
            if temps is None:                   # statically greedy batch:
                row_keys = None                 # no RNG / sort in the loop
            else:
                cnts = clens - prompt_lens + 1  # tokens sampled so far
                row_keys = jax.vmap(jax.random.fold_in)(keys, cnts)
            nxt = sample_batched(logits[:, 0], row_keys, temperature=temps,
                                 top_k=top_ks, vocab_limit=self.cfg.vocab_size)
            emit = ~done
            last = jnp.where(emit, nxt, last)
            clens = clens + emit.astype(jnp.int32)
            rem = rem - emit.astype(jnp.int32)
            done = done | (emit & ((rem <= 0) | (nxt == eos)
                                   | (clens >= self.capacity - 1)))
            tb = tb.at[i].set(jnp.where(emit, nxt, 0))
            eb = eb.at[i].set(emit)
            return (i + 1, cache, last, clens, rem, done, tb, eb)

        st = (jnp.int32(0), cache, last_tok, cache_lens, remaining, done,
              tok_buf, emit_buf)
        _, cache, last_tok, cache_lens, remaining, done, tok_buf, emit_buf = \
            jax.lax.while_loop(cond, body, st)
        return cache, tok_buf, emit_buf, cache_lens, remaining, done

    # ---- speculative decode: jit'd verify + accept + rewind ----------------
    def _verify_fn(self, params, cache, tokens, clens, lens, temps, top_ks,
                   key, block_tables=None):
        """One batched speculative verify step for every slot — any arch.

        tokens [B, S]: ``[last, d_1 .. d_k, pad]`` per row (S = spec_len+1),
        lens [B] = k+1 valid inputs (0 for rows sitting this verify out —
        empty, done, or undrafted slots: no writes, no commits; undrafted
        slots take the chunked decode loop this step instead). One forward
        scores all draft positions (staging per-position states for stateful
        blocks); accept_batched picks the matched prefix + a correction/
        bonus token per drafted row; ``model.verify_commit`` then rewinds
        every stateful block to its row's accepted length with gathers /
        ring splices — all inside this one jit, no per-slot replay.
        """
        positions = clens[:, None] + jnp.arange(tokens.shape[1],
                                                dtype=jnp.int32)[None, :]
        batch = {"tokens": tokens, "positions": positions}
        logits, staged = self.model.verify(params, batch, cache, clens,
                                           lens=lens,
                                           block_tables=block_tables)
        out_tok, out_len = accept_batched(
            logits, tokens, jnp.maximum(lens - 1, 0), key,
            temperature=temps, top_k=top_ks,
            vocab_limit=self.cfg.vocab_size)
        cache = self.model.verify_commit(staged, clens, out_len, lens)
        return cache, out_tok, out_len

    # ---- per-prefix snapshot splices (snapshot mode) -----------------------
    def _snap_capture_fn(self, arena, cache, sid, slot):
        """Copy slot ``slot``'s complete state row into arena row ``sid``."""
        return slot_splice(arena, slot_extract(cache, slot), sid)

    def _snap_restore_fn(self, cache, arena, sid, slot):
        """Restore arena row ``sid`` into slot ``slot`` — equivalent to
        having prefilled the snapshot's prefix into that slot."""
        return slot_splice(cache, slot_extract(arena, sid), slot)
