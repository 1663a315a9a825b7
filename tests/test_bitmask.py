"""Packed-bitmask invariants: round-trip, on-device compaction, ragged-tile
errors.

The packed uint32 mask (32 R-neighbours per word) is the wire format
between the fused kernel and candidate extraction; these tests pin down
its algebra: ``unpack(pack(x)) == x``, popcount/prefix-sum compaction
equals the ``np.nonzero`` oracle, and a non-multiple-of-32 R tile raises
instead of silently truncating.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.engine import extract
from repro.kernels.fused_cnf_join import ref as cnf_ref
from repro.kernels.fused_cnf_join.kernel import VEC, cnf_join_block


# --- round-trip -------------------------------------------------------------

@pytest.mark.parametrize("seed,shape", [
    (0, (8, 32)), (1, (33, 64)), (2, (5, 128)), (3, (1, 32)), (4, (64, 96)),
])
def test_pack_unpack_roundtrip(seed, shape):
    rng = np.random.default_rng(seed)
    ok = rng.random(shape) < rng.uniform(0.05, 0.9)
    packed = np.asarray(cnf_ref.pack_mask(jnp.asarray(ok)))
    assert packed.dtype == np.uint32
    assert packed.shape == (shape[0], shape[1] // 32)
    back = cnf_ref.unpack_mask(packed, shape[1])
    assert np.array_equal(back, ok)


def test_unpack_narrower_than_packed():
    """unpack_mask(p, n_r) drops padding columns beyond n_r."""
    ok = np.zeros((4, 64), bool)
    ok[2, 50] = True
    ok[1, 3] = True
    packed = np.asarray(cnf_ref.pack_mask(jnp.asarray(ok)))
    back = cnf_ref.unpack_mask(packed, 40)
    assert back.shape == (4, 40)
    assert back[1, 3] and not back.any(axis=1)[2]


# --- on-device compaction vs np.nonzero oracle ------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_extraction_matches_nonzero_oracle(seed):
    rng = np.random.default_rng(100 + seed)
    nl = int(rng.integers(1, 40))
    nw = int(rng.integers(1, 6))
    ok = rng.random((nl, nw * 32)) < rng.uniform(0.0, 0.6)
    packed = jnp.asarray(np.asarray(cnf_ref.pack_mask(jnp.asarray(ok))))
    cap = int(ok.sum()) + 8
    buf, count = extract.extract_pairs(packed, capacity=cap)
    count = int(count)
    assert count == int(ok.sum())
    got = sorted(map(tuple, np.asarray(buf[:count]).tolist()))
    ii, jj = np.nonzero(ok)
    want = sorted(zip(ii.tolist(), jj.tolist()))
    assert got == want
    # filler untouched past count
    assert np.all(np.asarray(buf[count:]) == -1)


def test_extraction_applies_offsets():
    ok = np.zeros((4, 32), bool)
    ok[0, 0] = ok[3, 31] = True
    packed = jnp.asarray(np.asarray(cnf_ref.pack_mask(jnp.asarray(ok))))
    buf, count = extract.extract_pairs(packed, capacity=4,
                                       row_offset=100, col_offset=1000)
    got = sorted(map(tuple, np.asarray(buf[: int(count)]).tolist()))
    assert got == [(100, 1000), (103, 1031)]


def test_extraction_overflow_detected_not_silent():
    """count keeps growing past capacity so the caller can detect + retry."""
    ok = np.ones((8, 32), bool)                  # 256 candidates
    packed = jnp.asarray(np.asarray(cnf_ref.pack_mask(jnp.asarray(ok))))
    buf, count = extract.extract_pairs(packed, capacity=10)
    assert int(count) == 256                     # true total, not clamped
    # the first `capacity` slots hold valid pairs, nothing corrupted
    got = np.asarray(buf)
    assert got.shape == (10, 2)
    assert (got >= 0).all()


def test_extraction_append_across_chunks():
    """compact_append accumulates two chunks exactly like one big extract."""
    rng = np.random.default_rng(7)
    ok1 = rng.random((16, 64)) < 0.3
    ok2 = rng.random((16, 64)) < 0.3
    p1 = jnp.asarray(np.asarray(cnf_ref.pack_mask(jnp.asarray(ok1))))
    p2 = jnp.asarray(np.asarray(cnf_ref.pack_mask(jnp.asarray(ok2))))
    cap = int(ok1.sum() + ok2.sum()) + 4
    buf = jnp.full((cap, 2), -1, jnp.int32)
    buf, cnt = extract.compact_append(p1, buf, jnp.zeros((), jnp.int32),
                                      row_offset=0, col_offset=0)
    buf, cnt = extract.compact_append(p2, buf, cnt, row_offset=0, col_offset=64)
    got = sorted(map(tuple, np.asarray(buf[: int(cnt)]).tolist()))
    full = np.concatenate([ok1, ok2], axis=1)
    ii, jj = np.nonzero(full)
    assert got == sorted(zip(ii.tolist(), jj.tolist()))


# --- blocked search vs the whole-buffer search ------------------------------

B = extract._BLOCK


def _search_all_slots(packed, buf, count, *, row_offset=0, col_offset=0):
    """The whole-buffer search ``compact_append`` replaced, kept as the
    reference: every one of the buffer's slots binary-searches the prefix
    sum, and a final select keeps the slots that hold a candidate."""
    capacity = buf.shape[0]
    nw = packed.shape[1]
    flat = packed.reshape(-1)
    counts = lax.population_count(flat).astype(jnp.int32)
    cum = jnp.cumsum(counts)
    total = cum[-1]
    slot = jnp.arange(capacity, dtype=jnp.int32) - count
    word = jnp.clip(jnp.searchsorted(cum, slot, side="right"),
                    0, flat.shape[0] - 1).astype(jnp.int32)
    rank = slot - (cum[word] - counts[word])
    bits = ((flat[word][:, None] >> jnp.arange(32, dtype=jnp.uint32))
            & jnp.uint32(1)).astype(jnp.int32)
    bit = jnp.sum(jnp.cumsum(bits, axis=-1) <= rank[:, None], axis=-1,
                  dtype=jnp.int32)
    pairs = jnp.stack([word // nw + row_offset,
                       (word % nw) * 32 + bit + col_offset], axis=-1)
    fill = (slot >= 0) & (slot < total)
    return jnp.where(fill[:, None], pairs, buf), count + total


def _mask_with(total, seed, nw=8):
    """A packed (nl, nw) mask with exactly ``total`` set bits, room for
    3B + 64 of them, and its unpacked form."""
    nl = -(-(3 * B + 64) // (nw * 32))
    rng = np.random.default_rng(seed)
    ok = np.zeros(nl * nw * 32, bool)
    ok[rng.choice(ok.size, total, replace=False)] = True
    ok = ok.reshape(nl, nw * 32)
    return jnp.asarray(np.asarray(cnf_ref.pack_mask(jnp.asarray(ok)))), ok


@pytest.mark.parametrize("total,capacity,count", [
    (0, 3 * B, 0),
    (B - 1, 3 * B, 0),
    (B, 3 * B, 0),
    (B + 1, 3 * B, 0),
    (2 * B + 1, 3 * B, 0),
    (2 * B + 50, 2 * B + 100, 0),          # capacity not a multiple of B
    (B + 7, 3 * B + 5, B // 2 + 3),        # append from mid-block
    (2 * B + 100, 2 * B + 100, 0),         # fills the buffer exactly
    (2 * B + 110, 2 * B + 100, 0),         # overflow past capacity
    (2 * B, 2 * B + 100, 300),             # append that overflows
    (50, 2 * B + 100, 2 * B + 100),        # count == capacity on entry
    (50, 2 * B + 100, 2 * B + 107),        # count > capacity on entry
    (700, 1000, 0),                        # capacity under one block
    (1500, 1000, 200),                     # ... and overflowing it
], ids=["total0", "B-1", "B", "B+1", "2B+1", "ragged-capacity",
        "mid-block-append", "exactly-full", "overflow", "append-overflow",
        "count-at-capacity", "count-past-capacity", "small-capacity",
        "small-capacity-overflow"])
def test_blocked_search_matches_whole_buffer_search(total, capacity, count):
    """compact_append's blocked search returns the same buffer, filler and
    prior contents included, and the same count as the search over every
    slot; its new rows are np.nonzero's pairs in order."""
    packed, ok = _mask_with(total, seed=total + capacity + count)
    rng = np.random.default_rng(count)
    prior = jnp.asarray(rng.integers(-9, 0, (capacity, 2)), jnp.int32)
    offs = {"row_offset": 5, "col_offset": 64}
    got_buf, got_cnt = jax.jit(lambda p, b, c: extract.compact_append(
        p, b, c, **offs))(packed, prior, jnp.int32(count))
    want_buf, want_cnt = _search_all_slots(packed, prior, jnp.int32(count),
                                           **offs)
    assert int(got_cnt) == int(want_cnt) == count + total   # never clamped
    assert np.array_equal(np.asarray(got_buf), np.asarray(want_buf))
    n_fill = max(0, min(total, capacity - count))
    ii, jj = np.nonzero(ok)
    oracle = np.stack([ii + 5, jj + 64], axis=-1)[:n_fill]
    got_buf = np.asarray(got_buf)
    assert np.array_equal(got_buf[count:count + n_fill], oracle)
    untouched = np.ones(capacity, bool)
    untouched[count:count + n_fill] = False
    assert np.array_equal(got_buf[untouched], np.asarray(prior)[untouched])
    if count == 0:                         # the one-shot form, -1 filler
        buf, cnt = extract.extract_pairs(packed, capacity=capacity, **offs)
        want_buf, _ = _search_all_slots(
            packed, jnp.full((capacity, 2), -1, jnp.int32), jnp.int32(0),
            **offs)
        assert int(cnt) == total
        assert np.array_equal(np.asarray(buf), np.asarray(want_buf))
        assert (np.asarray(buf)[n_fill:] == -1).all()


@pytest.mark.parametrize("counts,capacity,blocks", [
    ([0, 0], 400_384, 0),
    ([1, B - 1, B, B + 1], 400_384, 1 + 1 + 1 + 2),
    ([700, 0, 3 * B], 400_384, 1 + 0 + 3),
    ([10**7], 400_384, -(-400_384 // B)),  # overflow: the whole buffer
    ([700, 1000, 5000], 1000, 1 + 1 + 1),  # capacity under one block
])
def test_extract_blocks_counts_loop_trips(counts, capacity, blocks):
    """The host's count of the extraction's loop trips: ceil(min(count,
    capacity) / block) a shard, the block never wider than the buffer."""
    assert extract.extract_blocks(np.asarray(counts), capacity) == blocks


# --- ragged-tile errors -----------------------------------------------------

def test_pack_mask_rejects_ragged_width():
    with pytest.raises(ValueError, match="multiple of 32"):
        cnf_ref.pack_mask(jnp.zeros((4, 40), bool))


def test_kernel_rejects_ragged_tr():
    el = jnp.zeros((1, 64, 128), jnp.float32)
    er = jnp.zeros((1, 48, 128), jnp.float32)
    sl = jnp.zeros((1, 64), jnp.float32)
    sr = jnp.zeros((1, 48), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 32"):
        cnf_join_block(el, er, sl, sr, (((VEC, 0),),), (0.5,),
                       tl=64, tr=48, interpret=True)


def test_kernel_rejects_untiled_shapes():
    el = jnp.zeros((1, 60, 128), jnp.float32)    # 60 % 32 != 0
    er = jnp.zeros((1, 64, 128), jnp.float32)
    sl = jnp.zeros((1, 60), jnp.float32)
    sr = jnp.zeros((1, 64), jnp.float32)
    with pytest.raises(ValueError, match="pack_features"):
        cnf_join_block(el, er, sl, sr, (((VEC, 0),),), (0.5,),
                       tl=32, tr=32, interpret=True)


def test_sharded_engine_rejects_ragged_tr():
    from repro.engine.sharded import ShardedEngine
    with pytest.raises(ValueError, match="multiple of 32"):
        ShardedEngine(tr=48)
    with pytest.raises(ValueError, match="multiple of tr"):
        ShardedEngine(tr=32, r_chunk=40)
