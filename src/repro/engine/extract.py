"""On-device candidate extraction from the packed CNF bitmask.

The fused kernel emits a uint32 mask packed 32 R-neighbours per word.
Pulling that mask to the host costs n_l·n_r/8 bytes regardless of how few
pairs survive — at corpus scale the transfer, not the kernel, dominates.
``compact_append`` turns the mask into a dense buffer of (i, j) index
pairs *on the device* via popcount + prefix-sum compaction, written as a
gather so its cost follows the candidates, not the mask or the buffer:

  1. ``lax.population_count`` per word -> inclusive prefix sum over words
     (row-major);
  2. only the slots the call fills, n_fill = min(total, capacity - count),
     are searched, in blocks of B = ``_BLOCK`` inside a loop whose trip
     count is the device's own ceil(n_fill / B): for each slot a binary
     search of that prefix sum finds the word holding the slot's set bit,
     and the in-word prefix count picks the bit;
  3. slots past the candidates keep the buffer's previous contents.

A scatter of every bit of the mask would cost O(n_l·n_r) scatter
updates — about 3 s per 100,352 x 512 band step on a TPU v5e.  A search
of every slot of that step's 400,384-row buffer cost 72 ms there,
O(capacity · log(words) + words); the blocked search costs
O(words + ceil(n_fill / B) · B · log(words)): 2.25 ms there, one block,
at the 800-3,000 candidates a step of a sparse join.

The buffer has a fixed capacity (shapes must be static under jit);
overflow is *detected, never silent* — the returned count keeps growing
past capacity, so the caller compares count vs capacity and retries
bigger.  What the host keeps becomes O(candidates): one scalar count plus
8 bytes per surviving pair (the sharded engine's copy still moves each
non-empty shard's whole buffer, ``engine/sharded.py``).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

_CAP_QUANTUM = 1024                    # capacities round up to this
_BLOCK = 4096                          # buffer slots searched a loop trip


def grow_caps(caps, counts):
    """Per-shard capacity growth after an overflowed step (DESIGN.md §3).

    caps:   int array, one sweep-carried capacity per (pod, data, model)
            shard; counts: that step's exact per-shard candidate counts
            (``compact_append`` never clamps, so they are true totals).

    Only shards whose count exceeded their capacity grow — each to
    ``max(4 * its own capacity, count rounded up to 1 KiB of rows)``.  The
    ≥4× rule bounds retries per shard; applying it *per shard* means one
    hot shard no longer compounds the whole sweep's buffer: the uniform
    SPMD dispatch capacity is ``caps.max()``, and a later overflow on a
    previously-cold shard grows from that shard's own small capacity, not
    from the hot shard's inflated one.  Returns a new array; input caps
    are never shrunk.
    """
    caps = np.asarray(caps, np.int64)
    counts = np.asarray(counts, np.int64)
    need = -(-counts // _CAP_QUANTUM) * _CAP_QUANTUM
    return np.where(counts > caps, np.maximum(4 * caps, need), caps)


def compact_append(packed, buf, count, *, row_offset=0, col_offset=0):
    """Append the set bits of ``packed`` to ``buf`` as (i, j) pairs.

    packed: uint32 (nl, nw) mask (nw words of 32 R-columns each)
    buf:    int32 (capacity, 2) output buffer
    count:  int32 scalar — pairs already in ``buf``; the write cursor
    row_offset/col_offset: global coordinates of packed[0, 0]'s bit 0
      (traced values are fine — e.g. ``lax.axis_index`` inside shard_map)

    Returns (buf, new_count).  new_count may exceed capacity — that means
    the tail was dropped and the caller must retry with a larger buffer.
    """
    capacity = buf.shape[0]
    nw = packed.shape[1]
    flat = packed.reshape(-1)
    counts = lax.population_count(flat).astype(jnp.int32)
    cum = jnp.cumsum(counts)                                         # inclusive
    total = cum[-1]
    n_fill = jnp.clip(jnp.minimum(total, capacity - count), 0)      # slots
    block = min(_BLOCK, capacity)
    lanes = jnp.arange(block, dtype=jnp.int32)

    def fill_block(b, buf):
        # the block's first row, kept inside the buffer: a last block that
        # would cross the end overlaps its predecessor and rewrites the
        # same pairs there
        start = jnp.minimum(count + b * block, capacity - block)
        slot = start + lanes - count                       # rank of the bit
        word = jnp.clip(jnp.searchsorted(cum, slot, side="right"),
                        0, flat.shape[0] - 1).astype(jnp.int32)
        rank = slot - (cum[word] - counts[word])                     # in word
        bits = ((flat[word][:, None] >> jnp.arange(32, dtype=jnp.uint32))
                & jnp.uint32(1)).astype(jnp.int32)               # (block,32)
        bit = jnp.sum(jnp.cumsum(bits, axis=-1) <= rank[:, None], axis=-1,
                      dtype=jnp.int32)
        pairs = jnp.stack([word // nw + row_offset,
                           (word % nw) * 32 + bit + col_offset], axis=-1)
        fill = (slot >= 0) & (slot < n_fill)
        old = lax.dynamic_slice_in_dim(buf, start, block)
        return lax.dynamic_update_slice_in_dim(
            buf, jnp.where(fill[:, None], pairs, old), start, axis=0)

    buf = lax.fori_loop(0, -(-n_fill // block), fill_block, buf)
    return buf, count + total


def extract_blocks(counts, capacity):
    """Loop trips ``extract_pairs`` made at ``capacity``, summed over the
    given per-shard candidate counts: ``ceil(min(count, capacity) / B)``
    each, B the block of slots one trip fills.  Host arithmetic on counts
    the host already holds; the device does no extra work for it."""
    block = min(_BLOCK, capacity)
    fill = np.minimum(np.asarray(counts, np.int64), capacity)
    return int((-(-fill // block)).sum())


def hierarchical_offsets(count, *, inner_axes, inner_index, pod_axis=None):
    """Global exclusive offset of this device's candidates, prefix-summed
    hierarchically: within the pod first, then across pods (DESIGN.md §3).

    count:       int32 scalar — this device's candidate count
    inner_axes:  mesh axis names spanning one pod (e.g. ("data", "model"))
    inner_index: this device's linear index over ``inner_axes`` (row-major
                 in the given axis order) — a traced value from
                 ``lax.axis_index`` composition
    pod_axis:    the cross-pod axis name, or None on a single-pod mesh

    Two collectives, both over *counts only*:

      1. ``all_gather(count, inner_axes)`` — within-pod, one int32 per
         device in the pod; the exclusive cumsum at ``inner_index`` is the
         device's base inside its pod;
      2. ``all_gather(pod_total, pod_axis)`` — the **only cross-pod
         collective in the engine**, one int32 per pod.  This is the
         multi-pod design invariant the dry-run asserts via
         ``distributed.hlo_analysis``: inter-pod links carry candidate
         counts, never feature planes or masks.

    Returns (global_base int32, pod_counts) where ``pod_counts`` is the
    within-pod gathered count vector (the host cross-checks its emission
    bookkeeping against the returned bases).
    """
    pod_counts = lax.all_gather(count, inner_axes)            # (pod devs,)
    excl = jnp.cumsum(pod_counts) - pod_counts
    base = excl[inner_index]
    if pod_axis is None:
        return base, pod_counts
    pod_total = pod_counts.sum()
    totals = lax.all_gather(pod_total, pod_axis)              # counts only
    p = lax.axis_index(pod_axis)
    pod_base = (jnp.cumsum(totals) - totals)[p]
    return pod_base + base, pod_counts


def extract_pairs(packed, *, capacity, row_offset=0, col_offset=0):
    """One-shot compaction of a packed mask into a fresh buffer.

    Returns (buf int32 (capacity, 2), count int32).  Entries past ``count``
    are -1 filler; count > capacity signals overflow (see compact_append).
    """
    buf = jnp.full((capacity, 2), -1, jnp.int32)
    return compact_append(packed, buf, jnp.zeros((), jnp.int32),
                          row_offset=row_offset, col_offset=col_offset)
