"""KV-cache slot slicing / merging — the data plane of disaggregated serving.

A continuous batcher's cache is a pytree whose leaves carry a batch ("slot")
dimension at a family-dependent axis (layer-stacked KV slices put it at
axis 1, doubly-stacked hybrid caches at axis 2, ...).  These helpers derive
the batch-axis index per leaf from the cache *specs* (each :class:`PSpec`
names its logical axes, so the position of ``"batch"`` is exact, not
guessed) and then slice whole per-request rows out of one cache or merge
them into free slots of another.

This is what moves over an :class:`~repro.core.channels.ArrayChannel` in the
prefill-cell -> decode-cell handoff: the prefill cell slices one request's
KV rows, the channel reshards them onto the decode cell's mesh, and the
decode cell merges them into a free batcher slot.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.models.layers import KVSlice, PagedKVCache
from repro.models.param import tree_map_pspec


def cache_batch_axes(model, batch: int, max_len: int) -> Any:
    """Tree (same structure as the cache) of per-leaf batch-axis indices."""
    return tree_map_pspec(
        lambda s: s.logical.index("batch"),
        model.cache_specs(batch, max_len),
    )


def slice_cache_slots(cache: Any, axes: Any, slots: Sequence[int]) -> Any:
    """Gather the given slot rows out of every cache leaf.

    Returns a cache whose batch dimension is ``len(slots)``; the original
    cache is untouched.
    """
    idx = jnp.asarray(slots, jnp.int32)
    return jax.tree.map(lambda c, a: jnp.take(c, idx, axis=a), cache, axes)


def merge_cache_slots(dst: Any, src: Any, axes: Any, slots: Sequence[int]) -> Any:
    """Write ``src`` rows (batch dim == len(slots)) into ``dst`` at ``slots``.

    Runs eagerly; on a multi-device cache the scatter may gather/reshard —
    the handoff path sends per-request rows already placed on the
    destination mesh, so this stays local in the common case.
    """
    idx = jnp.asarray(slots, jnp.int32)

    def put(d, s, a):
        return d.at[(slice(None),) * a + (idx,)].set(s)

    return jax.tree.map(put, dst, src, axes)


def install_cross_memory(cache: Any, mem, slots: Sequence[int]) -> Any:
    """Write per-request encdec cross-attention memory into batcher slots.

    ``mem`` = (cross_k (L, B, S_src, Hkv, Dh), cross_v, src_len (B,)) with
    B == len(slots) — the return shape of ``Model.encode_cross_rows``.
    Used by the token-at-a-time prompt path: the chunked path gets its
    cross memory from ``prefill_ranged``'s cache instead.
    """
    ck, cv, src_len = mem
    dec = cache["dec_layers"]
    idx = jnp.asarray(slots, jnp.int32)
    out = dict(cache)
    out["dec_layers"] = dec._replace(
        cross_k=dec.cross_k.at[:, idx].set(ck.astype(dec.cross_k.dtype)),
        cross_v=dec.cross_v.at[:, idx].set(cv.astype(dec.cross_v.dtype)),
        src_len=dec.src_len.at[:, idx].set(src_len[None, :]),
    )
    return out


# --------------------------------------------------------------------------
# paged KV: canonical page layout + block-table indirection
# --------------------------------------------------------------------------
# A *page* is ``page_size`` consecutive positions of ONE request's KV across
# every positional cache leaf (all layers at once).  The canonical page
# layout moves each KVSlice leaf's batch axis to the front and its
# positions axis next to the innermost dim — k/v ``(num_pages, *stack,
# Hkv, page_size, Dh)``, slot_pos ``(num_pages, *stack, page_size)`` — so
# one integer page id addresses the same positions in every leaf, whatever
# that leaf's stacking depth is (layer-stacked dense caches, group-stacked
# hybrid shared KV, ...), and one (page, layer, KV head) is a contiguous
# ``(page_size, Dh)`` tile: the block the paged Pallas kernels read.  The
# block table maps ``(slot, logical_page) -> physical_page``; entries >=
# ``num_pages`` are UNMAPPED sentinels: gathers fill (k/v = 0, slot_pos =
# -1, i.e. position-masked) and scatters drop, so an unmapped page is
# indistinguishable from an empty one and a write to it is a no-op.

# canonical positions axis of each KVSlice field
_POS_AXIS = KVSlice(k=-2, v=-2, slot_pos=-1)


def _is_kv(x) -> bool:
    return isinstance(x, (KVSlice, PagedKVCache))


def kv_cache_nodes(cache: Any) -> list:
    """The cache's KVSlice nodes in pytree flatten order."""
    return [n for n in jax.tree.leaves(cache, is_leaf=_is_kv) if _is_kv(n)]


def strip_kv_nodes(cache: Any) -> Any:
    """The cache with every KVSlice subtree pruned (replaced by None) —
    the *resident* part that stays dense per-slot (encdec cross memory;
    nothing at all for dense/moe)."""
    return jax.tree.map(lambda n: None if _is_kv(n) else n, cache,
                        is_leaf=_is_kv)


def rebuild_kv_nodes(template: Any, resident: Any, nodes: list) -> Any:
    """Inverse of ``strip_kv_nodes``: splice ``nodes`` (flatten order)
    back into ``resident`` using the spec ``template`` for structure."""
    it = iter(nodes)
    return jax.tree.map(
        lambda t, r: next(it) if _is_kv(t) else r, template, resident,
        is_leaf=_is_kv,
    )


def kv_node_axes(model, batch: int, max_len: int) -> list:
    """Per-KVSlice-node batch-axis index (seq is always batch+1)."""
    return [n.k.logical.index("batch")
            for n in kv_cache_nodes(model.cache_specs(batch, max_len))]


def kv_position_bytes(model, max_len: int) -> int:
    """Bytes of KV cache held per token position (all layers, one slot) —
    the unit behind the ``kv_bytes_saved`` accounting."""
    total = 0
    for node in kv_cache_nodes(model.cache_specs(1, max_len)):
        for spec in (node.k, node.v, node.slot_pos):
            n = 1
            for d in spec.shape:
                n *= d
            itemsize = jnp.dtype(spec.dtype or model.cfg.dtype).itemsize
            total += n * itemsize // max_len
    return total


def recurrent_state_bytes(model, max_len: int) -> int:
    """Bytes of one slot's NON-positional cache state (everything that is
    not a KVSlice: mamba conv + ssm tensors, hybrid group states) — the
    size of one recurrent-state snapshot, and the unit behind the
    ``snapshot_bytes_saved`` accounting."""
    total = 0
    for spec in jax.tree.leaves(strip_kv_nodes(model.cache_specs(1, max_len))):
        n = 1
        for d in spec.shape:
            n *= d
        total += n * jnp.dtype(spec.dtype or model.cfg.dtype).itemsize
    return total


def clear_kv_row(cache: Any, axes: list, row: int) -> Any:
    """Invalidate every KV position of one slot row (``slot_pos`` -> -1)
    so a snapshot restore into a recycled slot can never leave stale
    attendable positions behind the restored prefix."""
    nodes = kv_cache_nodes(cache)
    resident = strip_kv_nodes(cache)
    out_nodes = []
    for node, a in zip(nodes, axes):
        sp = jnp.moveaxis(node.slot_pos, a, 0).at[row].set(-1)
        out_nodes.append(node._replace(slot_pos=jnp.moveaxis(sp, 0, a)))
    return rebuild_kv_nodes(cache, resident, out_nodes)


def _to_canonical(node: KVSlice, axis: int) -> KVSlice:
    """Dense KV node (batch at ``axis``, seq at ``axis + 1``) -> canonical
    layout (batch first, positions at ``_POS_AXIS``)."""
    return jax.tree.map(
        lambda x, p: jnp.moveaxis(x, (axis, axis + 1), (0, p)),
        node, _POS_AXIS)


def _from_canonical(node: KVSlice, axis: int) -> KVSlice:
    return jax.tree.map(
        lambda x, p: jnp.moveaxis(x, (0, p), (axis, axis + 1)),
        node, _POS_AXIS)


def _split_positions(x, p: int, page_size: int):
    """Canonical array -> pages first: positions axis ``p`` (of length
    n*P) splits into (n, P) and n moves to the front."""
    i = x.ndim + p
    x = x.reshape(x.shape[:i] + (x.shape[i] // page_size, page_size)
                  + x.shape[i + 1:])
    return jnp.moveaxis(x, i, 0)


def _merge_pages(x, p: int):
    """Inverse of :func:`_split_positions` (without the leading batch):
    page stacks (n, ..., P at ``p``, ...) -> (..., n*P, ...)."""
    x = jnp.moveaxis(x, 0, x.ndim + p - 1)
    i = x.ndim + p - 1
    return x.reshape(x.shape[:i] + (x.shape[i] * x.shape[i + 1],)
                     + x.shape[i + 2:])


def page_arena(model, num_pages: int, page_size: int) -> list:
    """Physical page arena: one canonical page-layout KVSlice per
    positional cache node.  Built from ``init_cache`` so k/v start zeroed
    and ``slot_pos`` starts -1 (every page empty)."""
    full = model.init_cache(num_pages, page_size)
    axes = kv_node_axes(model, num_pages, page_size)
    return [_to_canonical(n, a) for n, a in zip(kv_cache_nodes(full), axes)]


def gather_pages(arena: list, axes: list, block_table: jnp.ndarray,
                 page_size: int) -> list:
    """Materialize dense per-slot KV nodes from the arena through the
    block table (jit-traceable).  ``block_table``: (B, n_logical) int32,
    entries >= num_pages gather as empty (k/v 0, slot_pos -1)."""
    out = []
    for node, a in zip(arena, axes):
        def g(x, p, fill):
            y = jnp.take(x, block_table, axis=0, mode="fill",
                         fill_value=fill)              # (B, n_log, *page)
            return jax.vmap(lambda r: _merge_pages(r, p))(y)
        dense = KVSlice(k=g(node.k, -2, 0), v=g(node.v, -2, 0),
                        slot_pos=g(node.slot_pos, -1, -1))
        out.append(_from_canonical(dense, a))
    return out


def extract_row_pages(cache: Any, axes: list, row: int, start_page: int,
                      n_pages: int, page_size: int) -> list:
    """Slice ``n_pages`` canonical page stacks (one (n_pages, ...) array
    per k/v/slot_pos of each KV node) out of one row of a dense cache —
    the page-granular payload of the prefill -> decode handoff."""
    out = []
    lo, hi = start_page * page_size, (start_page + n_pages) * page_size
    for node, a in zip(kv_cache_nodes(cache), axes):
        def e(x, p):
            x = x[row]
            x = jax.lax.slice_in_dim(x, lo, hi, axis=x.ndim + p)
            return _split_positions(x, p, page_size)
        out.append(jax.tree.map(e, _to_canonical(node, a), _POS_AXIS))
    return out


def write_arena_pages(arena: list, page_ids, stacks: list) -> list:
    """Write canonical page stacks into the arena at ``page_ids``."""
    idx = jnp.asarray(page_ids, jnp.int32)
    return [
        KVSlice(k=a.k.at[idx].set(s.k.astype(a.k.dtype)),
                v=a.v.at[idx].set(s.v.astype(a.v.dtype)),
                slot_pos=a.slot_pos.at[idx].set(s.slot_pos))
        for a, s in zip(arena, stacks)
    ]


def read_arena_pages(arena: list, page_ids) -> list:
    """Canonical page stacks for ``page_ids`` (inverse of write)."""
    idx = jnp.asarray(page_ids, jnp.int32)
    return [KVSlice(k=a.k[idx], v=a.v[idx], slot_pos=a.slot_pos[idx])
            for a in arena]


def clean_arena_pages(arena: list, page_ids) -> list:
    """Mark every position of the given pages empty (``slot_pos`` -1) so
    a recycled page's stale contents can never be attended."""
    idx = jnp.asarray(page_ids, jnp.int32)
    return [a._replace(slot_pos=a.slot_pos.at[idx].set(-1)) for a in arena]


# --------------------------------------------------------------------------
# native paged views: the arena itself flows through Model.decode
# --------------------------------------------------------------------------


def paged_view(template: Any, resident: Any, arena: list,
               block_table: jnp.ndarray, scales=None) -> Any:
    """Build the cache pytree that carries the arena THROUGH the model.

    Each positional KV node becomes a :class:`PagedKVCache` wrapping the
    whole arena node plus the batch's block table (``layer`` starts 0; the
    layer scan rebinds it per step — see ``Model._scan_stack``).  The
    resident tree contributes everything that stays dense per-slot (encdec
    cross memory).  ``scales``: per-node ``(k_scale, v_scale)`` list for
    int8 arenas, or None.
    """
    nodes = []
    for i, a in enumerate(arena):
        ks, vs = (scales[i] if scales is not None else (None, None))
        nodes.append(PagedKVCache(
            k=a.k, v=a.v, slot_pos=a.slot_pos, block_table=block_table,
            layer=jnp.zeros((), jnp.int32), k_scale=ks, v_scale=vs,
        ))
    return rebuild_kv_nodes(template, resident, nodes)


def extract_paged(cache: Any):
    """Inverse of :func:`paged_view`: (arena nodes, scales, resident)."""
    nodes = kv_cache_nodes(cache)
    arena = [KVSlice(k=n.k, v=n.v, slot_pos=n.slot_pos) for n in nodes]
    scales = [(n.k_scale, n.v_scale) for n in nodes]
    if all(k is None for k, _ in scales):
        scales = None
    return arena, scales, strip_kv_nodes(cache)


# --------------------------------------------------------------------------
# int8 KV pages: per-page symmetric quantization
# --------------------------------------------------------------------------


def _bshape(ndim: int, keep_axes, scale_shape) -> tuple:
    shape = [1] * ndim
    for a, s in zip(keep_axes, scale_shape):
        shape[a] = s
    return tuple(shape)


def quantize_page(x: jnp.ndarray, *, keep_axes=(0,)):
    """Symmetric int8 quantization with one scale per kept-axes index.

    ``keep_axes`` (sorted ascending) name the axes that keep their own
    scale — e.g. ``(0, 1)`` on a canonical ``(n_pages, L, Hkv, P, Dh)``
    page stack gives one scale per (page, layer).  Returns
    ``(q int8, scale f32)`` with ``scale.shape == tuple(x.shape[a] for a
    in keep_axes)``.  All-zero groups get scale 0 (dequantizes to 0).
    """
    x32 = x.astype(jnp.float32)
    red = tuple(a for a in range(x.ndim) if a not in keep_axes)
    amax = jnp.max(jnp.abs(x32), axis=red)
    scale = amax / 127.0
    b = scale.reshape(_bshape(x.ndim, keep_axes, scale.shape))
    q = jnp.clip(jnp.round(x32 / jnp.maximum(b, 1e-8)), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_page(q: jnp.ndarray, scale: jnp.ndarray, *, keep_axes=(0,)):
    """Inverse of :func:`quantize_page` (f32 output)."""
    b = scale.reshape(_bshape(q.ndim, keep_axes, scale.shape))
    return q.astype(jnp.float32) * b


def load_pages_into_row(cache: Any, template: Any, axes: list, row: int,
                        stacks: list, start_page: int, page_size: int) -> Any:
    """Write canonical page stacks into one row of a dense cache at
    logical pages ``start_page..`` — how a shared prefix becomes the
    resident context of an extend-prefill scratch row."""
    nodes = kv_cache_nodes(cache)
    resident = strip_kv_nodes(cache)
    lo = start_page * page_size
    out_nodes = []
    for node, stack, a in zip(nodes, stacks, axes):
        def w(x, s, p):
            flat = _merge_pages(s, p).astype(x.dtype)
            r = jax.lax.dynamic_update_slice_in_dim(
                x[row], flat, lo, axis=flat.ndim + p)
            return x.at[row].set(r)
        out_nodes.append(_from_canonical(
            jax.tree.map(w, _to_canonical(node, a), stack, _POS_AXIS), a))
    return rebuild_kv_nodes(template, resident, out_nodes)


def mask_pad_slots(cache: Any, length: jnp.ndarray) -> Any:
    """Invalidate cache slots beyond each row's true prompt length.

    Chunked prefill pads prompts to a bucket length, so positions
    ``length[b] .. S_pad-1`` hold garbage K/V.  Marking their ``slot_pos``
    as -1 makes the decode attention mask them out (``valid &= pos >= 0``)
    until the decode loop overwrites them with real tokens.
    """
    def fix(node):
        if isinstance(node, KVSlice):
            s_c = node.slot_pos.shape[-1]
            valid = jnp.arange(s_c, dtype=jnp.int32) < length[:, None]
            return node._replace(
                slot_pos=jnp.where(valid, node.slot_pos, jnp.int32(-1))
            )
        return node

    return jax.tree.map(fix, cache, is_leaf=lambda x: isinstance(x, KVSlice))
