"""Partitioner tests — reference remainder semantics (server.c:185-216)."""

import numpy as np
import pytest

from dsort_tpu.data.partition import (
    equal_partition,
    pad_kv_to_shards,
    pad_to_layout,
    pad_to_shards,
    partition,
)
from dsort_tpu.ops.local_sort import sentinel_for


def test_equal_partition_reference_semantics():
    # server.c:185-196: base = total/num, first total%num workers get one extra.
    assert equal_partition(10_000, 4) == [2500, 2500, 2500, 2500]
    assert equal_partition(10, 4) == [3, 3, 2, 2]
    assert equal_partition(3, 4) == [1, 1, 1, 0]
    assert equal_partition(0, 4) == [0, 0, 0, 0]
    assert equal_partition(16_384, 4) == [4096] * 4  # reference max job


def test_equal_partition_uncapped():
    # The reference aborts above 4096/chunk (server.c:193-196); we must not.
    sizes = equal_partition(1_000_000, 8)
    assert sum(sizes) == 1_000_000
    assert max(sizes) - min(sizes) <= 1


def test_partition_concat_roundtrip():
    data = np.arange(103, dtype=np.int32)
    chunks = partition(data, 7)
    assert len(chunks) == 7
    np.testing.assert_array_equal(np.concatenate(chunks), data)


def test_pad_to_shards_layout():
    data = np.arange(10, dtype=np.int32)
    shards, counts = pad_to_shards(data, 4, multiple=8)
    assert shards.shape == (4, 8)
    np.testing.assert_array_equal(counts, [3, 3, 2, 2])
    sent = sentinel_for(np.int32)
    recovered = np.concatenate([shards[i, : counts[i]] for i in range(4)])
    np.testing.assert_array_equal(recovered, data)
    assert (shards[0, 3:] == sent).all()


def test_pad_kv_to_shards():
    keys = np.arange(10, dtype=np.int64)
    vals = np.stack([np.arange(10), np.arange(10) * 2], axis=1).astype(np.uint8)
    sk, sv, counts = pad_kv_to_shards(keys, vals, 3)
    rec_k = np.concatenate([sk[i, : counts[i]] for i in range(3)])
    rec_v = np.concatenate([sv[i, : counts[i]] for i in range(3)])
    np.testing.assert_array_equal(rec_k, keys)
    np.testing.assert_array_equal(rec_v, vals)


@pytest.mark.parametrize("n,w", [(0, 4), (1, 8), (7, 8), (64, 8)])
def test_pad_to_shards_edge_sizes(n, w):
    data = np.random.default_rng(0).integers(-100, 100, n).astype(np.int32)
    shards, counts = pad_to_shards(data, w)
    assert counts.sum() == n
    rec = np.concatenate([shards[i, : counts[i]] for i in range(w)]) if n else []
    np.testing.assert_array_equal(rec, data)


def _copy_layout(data, counts, cap, fill):
    """The padded copy, written out the plain way: row i holds its shard,
    then ``fill``."""
    out = np.full((len(counts), cap) + data.shape[1:], fill, dtype=data.dtype)
    off = 0
    for i, s in enumerate(counts):
        out[i, :s] = data[off : off + s]
        off += s
    return out


# (n, P, multiple, cap, strided, exact): exact when every shard is full and
# the input is C-contiguous, so the layout is a view of the input.
LAYOUT_CASES = [
    (64, 8, 8, None, False, True),
    (4096, 4, 8, None, False, True),
    (48, 3, 16, None, False, True),
    (40, 8, 1, 5, False, True),  # explicit cap equal to the shard
    (60, 4, 8, None, False, False),  # 15 a shard, rounded up to 16
    (10, 4, 8, None, False, False),  # remainder: shards 3, 3, 2, 2
    (64, 8, 8, 16, False, False),  # explicit cap above the shard
    (64, 8, 8, None, True, False),  # strided input
    (0, 4, 8, None, False, False),
]


def _layout_inputs(n, strided):
    rng = np.random.default_rng(n)
    keys = rng.integers(-1000, 1000, 2 * n if strided else n).astype(np.int32)
    payload = rng.integers(0, 256, (len(keys), 5)).astype(np.uint8)
    sec = rng.integers(0, 2**16, len(keys)).astype(np.uint16)
    if strided:
        keys, payload, sec = keys[::2], payload[::2], sec[::2]
    return keys, payload, sec


def _check_layout(out, data, counts, cap, fill, exact):
    np.testing.assert_array_equal(out, _copy_layout(data, counts, cap, fill))
    assert out.shape == (len(counts), cap) + data.shape[1:]
    assert np.shares_memory(out, data) == exact
    assert out.flags.writeable == (not exact)
    if exact:
        with pytest.raises(ValueError):
            out[0, 0] = 0
        assert data.flags.writeable  # the caller's array is untouched


@pytest.mark.parametrize("fn", ["pad_to_shards", "pad_kv_to_shards", "pad_to_layout"])
@pytest.mark.parametrize("n,w,multiple,cap,strided,exact", LAYOUT_CASES)
def test_pad_layout_is_a_view_exactly_when_no_pad_is_needed(
    fn, n, w, multiple, cap, strided, exact
):
    keys, payload, sec = _layout_inputs(n, strided)
    before = (keys.copy(), payload.copy(), sec.copy())
    sizes = np.asarray(equal_partition(n, w), np.int32)
    want_cap = cap if cap is not None else -(-max(sizes.max(), 1) // multiple) * multiple
    sent = sentinel_for(keys.dtype)
    if fn == "pad_to_shards":
        sk, counts = pad_to_shards(keys, w, multiple=multiple, cap=cap)
        _check_layout(sk, keys, counts, want_cap, sent, exact)
    elif fn == "pad_kv_to_shards":
        sk, sv, counts = pad_kv_to_shards(keys, payload, w, multiple=multiple, cap=cap)
        _check_layout(sk, keys, counts, want_cap, sent, exact)
        _check_layout(sv, payload, counts, want_cap, 0, exact)
    else:
        counts = sizes
        ss = pad_to_layout(sec, counts, want_cap, fill=7)
        _check_layout(ss, sec, counts, want_cap, 7, exact)
    np.testing.assert_array_equal(counts, sizes)
    assert counts.dtype == np.int32
    for a, b in zip((keys, payload, sec), before):
        np.testing.assert_array_equal(a, b)


def test_make_mesh_rejects_zero_worker_axis():
    # Regression: dp > device count must raise, not build a (dp, 0) mesh.
    import jax

    from dsort_tpu.config import ConfigError, MeshConfig
    from dsort_tpu.parallel.mesh import make_mesh

    with pytest.raises(ConfigError):
        make_mesh(MeshConfig(dp=16), jax.devices()[:8])
