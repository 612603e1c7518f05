"""Serving programs: chunked prefill + decode with sampling.

Two programs back the serving stack:

* ``prefill_step`` — consumes a whole (bucket-padded) prompt in ONE program
  invocation, writes the KV cache directly, and samples the first output
  token from the logits at the last real prompt position.  This is the
  TTFT-critical path: O(prompt_len / chunk) invocations instead of the
  O(prompt_len) decode calls of token-at-a-time prompt consumption.
* ``serve_step`` — one decode step over all busy batcher slots.

Prompts are padded to *chunk buckets* (multiples of the batcher's
``prefill_chunk``) so the number of distinct compiled prefill programs is
bounded by ``max_len / chunk`` rather than one per prompt length.

Chunked prefill is exact for EVERY registered family — the padding is
neutralized per family inside ``Model.prefill_ranged`` (KV slot masking /
SSD validity mask / ``src_len``-masked cross memory), not here: this layer
only buckets, pads and batches, and consults ``supports_chunked_prefill``
(backed by ``Model.chunked_prefill_exact``) for the one remaining layout
exception (rolling sliding-window caches).
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import DISABLED, FlightRecorder, recorder_of
from repro.models.model import Model

F32 = jnp.float32


def sample_tokens(logits, rng, temperature: float = 0.0):
    """logits (B, V) -> token ids (B,).  temperature 0 = greedy."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits.astype(F32) / temperature).astype(jnp.int32)


def bucket_len(prompt_len: int, chunk: int, max_len: int) -> int:
    """Pad a prompt length up to the next chunk multiple (capped at the
    cache length) so prefill programs compile once per bucket.

    The cap binds LAST: a bucket longer than the cache would push the
    prefill attention into its rolling-cache branch and silently discard
    the real prompt KV."""
    b = -(-prompt_len // chunk) * chunk
    return min(max(b, chunk), max_len)


def supports_chunked_prefill(model: Model, max_len: int) -> bool:
    """Is ``Model.prefill_ranged`` exact for this model at ``max_len``?

    Consults the model's own capability (``Model.chunked_prefill_exact`` —
    every registered family qualifies; see its docstring for the per-family
    mask semantics) plus one cache-LAYOUT condition: a rolling SWA buffer
    (``sliding_window < max_len``) keeps only the last ``window`` slots of
    the PADDED sequence, so a short row's real tokens would be shifted out
    by its pad tail — those configs stay on the token-at-a-time path.
    """
    cfg = model.cfg
    return model.chunked_prefill_exact and (
        cfg.sliding_window is None or cfg.sliding_window >= max_len
    )


def build_prefill_step(model: Model, temperature: float = 0.0,
                       checkpoint_every: Optional[int] = None) -> Callable:
    """prefill_step(params, cache, batch, rng) -> (first_tokens, logits, cache).

    ``batch`` = {tokens (B, S_pad), length (B,)}; ``cache`` is a fresh
    (B-row) cache whose buffers are NOT donated — callers reuse a scratch
    cache across requests since prefill rebuilds every KV leaf.

    ``checkpoint_every`` (ssm/hybrid snapshot pools): the third output
    becomes ``(cache, ckpts)`` with ``ckpts`` the stacked per-boundary
    recurrent-state checkpoints from ``Model.prefill_ranged`` — the rest
    of the batching protocol (``run_prefill_prompts`` / ``_group``) passes
    it through untouched, so checkpointing callers unpack the pair.
    """
    def prefill_step(params, cache, batch, rng):
        if checkpoint_every is None:
            logits, cache = model.prefill_ranged(params, batch, cache)
        else:
            logits, cache, ckpts = model.prefill_ranged(
                params, batch, cache, checkpoint_every=checkpoint_every)
            cache = (cache, ckpts)
        toks = sample_tokens(logits, rng, temperature)
        return toks, logits, cache
    return prefill_step


def build_extend_step(model: Model, temperature: float = 0.0) -> Callable:
    """extend_step(params, cache, batch, rng) -> (first_tokens, logits, cache).

    The suffix-only sibling of ``prefill_step`` (paged prefix sharing):
    ``batch`` = {tokens (B, S_ext), pos (B,) prefix offsets, length (B,)
    true suffix lengths}; ``cache`` is a dense scratch cache whose rows
    already hold each request's shared prefix (gathered from pool pages)
    with everything beyond it position-masked.  Buffers are not donated —
    callers reuse the scratch across invocations.
    """
    def extend_step(params, cache, batch, rng):
        logits, cache = model.prefill_extend(params, batch, cache)
        toks = sample_tokens(logits, rng, temperature)
        return toks, logits, cache
    return extend_step


def run_prefill_prompts(step_fn: Callable, params, scratch_cache, prompts,
                        *, chunk: int, max_len: int, rng,
                        model: Optional[Model] = None,
                        srcs: Optional[Sequence] = None,
                        rec: FlightRecorder = DISABLED):
    """Bucket-pad B same-bucket prompts and run ONE jitted ``prefill_step``.

    All NON-EMPTY prompts must share a bucket (``bucket_len`` of each
    equals the group bucket) so a batch compiles to one (B, S_pad)
    program; zero-length rows are normalized to dummy batch padding
    (``length`` 0, every slot masked) rather than bucketed — asserted
    here so a future bucket check can never reject its own padding.
    ``scratch_cache`` is a B-row cache reused across invocations.  Rows
    are independent under prefill attention/scan, so the batched
    invocation is bit-equivalent to B single-row invocations.  ``model``
    + ``srcs`` (per-row source features or None) add the family-specific
    batch extras via ``Model.ranged_batch_extras`` (encdec source
    features; {} for every other family).  The sync on the first tokens
    is ``rec``'s ``prefill.wait`` phase.  Returns (first_tokens list,
    B-row KV cache, advanced rng).
    """
    B = len(prompts)
    s_pad = bucket_len(max(len(p) for p in prompts), chunk, max_len)
    tokens = np.zeros((B, s_pad), np.int32)
    lengths = np.zeros((B,), np.int32)
    for i, p in enumerate(prompts):
        if len(p):
            assert bucket_len(len(p), chunk, max_len) == s_pad, (
                f"prompt {i} (len {len(p)}) belongs to bucket "
                f"{bucket_len(len(p), chunk, max_len)}, not {s_pad}"
            )
        tokens[i, :len(p)] = p
        lengths[i] = len(p)
    batch = {
        "tokens": jnp.asarray(tokens),
        "length": jnp.asarray(lengths),
    }
    if model is not None:
        batch.update(model.ranged_batch_extras(
            list(srcs) if srcs is not None else [None] * B, max_len))
    rng, sub = jax.random.split(rng)
    toks, _logits, cache = step_fn(params, scratch_cache, batch, sub)
    with rec.phase("prefill.wait", wait=True):
        toks = np.asarray(toks)
    return [int(t) for t in toks], cache, rng


def run_prefill_group(step_fn: Callable, params, scratch: Callable, reqs,
                      *, chunk: int, max_len: int, rng, model: Model,
                      accounting=None):
    """ONE prefill invocation over a same-bucket request group.

    The batch dim is padded to the next power of two with dummy
    zero-length rows (normalized/masked by :func:`run_prefill_prompts`,
    discarded by callers) so compiled prefill variants stay O(log
    capacity) per bucket; the dummy-row waste — real prefill compute — is
    recorded as ``prefill_dummy_rows`` in ``accounting``.  ``scratch`` is
    a ``batch -> cache`` factory (callers memoize theirs).  The single
    definition both the colocated batcher and the disaggregated
    PrefillWorker use, so the batching protocol cannot drift between
    them.  Returns (first_tokens, b_pad-row cache, advanced rng, b_pad).
    """
    B = len(reqs)
    b_pad = 1 << (B - 1).bit_length()
    prompts = [r.prompt for r in reqs]
    prompts += [np.zeros(0, np.int32)] * (b_pad - B)
    srcs = [getattr(r, "src", None) for r in reqs] + [None] * (b_pad - B)
    toks, cache, rng = run_prefill_prompts(
        step_fn, params, scratch(b_pad), prompts,
        chunk=chunk, max_len=max_len, rng=rng, model=model, srcs=srcs,
        rec=recorder_of(accounting),
    )
    if accounting is not None and b_pad != B:
        accounting.record_counter("prefill_dummy_rows", b_pad - B)
    return toks, cache, rng, b_pad


def run_prefill_prompt(step_fn: Callable, params, scratch_cache, prompt,
                       *, chunk: int, max_len: int, rng,
                       model: Optional[Model] = None, src=None):
    """Single-prompt wrapper over :func:`run_prefill_prompts`.

    Returns (first_token, 1-row KV cache, advanced rng)."""
    toks, row_cache, rng = run_prefill_prompts(
        step_fn, params, scratch_cache, [prompt],
        chunk=chunk, max_len=max_len, rng=rng, model=model, srcs=[src],
    )
    return toks[0], row_cache, rng


def build_serve_step(model: Model, temperature: float = 0.0) -> Callable:
    """serve_step(params, cache, batch) -> (next_tokens, logits, cache).

    ``batch`` = {tokens (B,1), pos (B,)}; the KV cache is donated by callers.
    """
    def serve_step(params, cache, batch, rng):
        logits, cache = model.decode(params, cache, batch)
        toks = sample_tokens(logits, rng, temperature)
        return toks, logits, cache
    return serve_step
