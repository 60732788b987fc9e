"""Per-chip local sort kernels (L0 of SURVEY.md's layer map).

The reference's compute kernel is a recursive top-down merge sort running on a
worker's CPU with per-merge mallocs (``client.c:140-173``), limited to 4,096
int32 keys (``client.c:10,91``).  The TPU-native kernel is ``jax.lax.sort``
under ``jit`` — XLA lowers it to a tuned on-chip sort — with Pallas/bitonic
variants in ``ops.bitonic`` / ``ops.pallas_sort``.  No recursion, no dynamic
shapes, no size cap beyond HBM.

Padding convention (static shapes): distributed phases carry fixed-size
buffers plus a valid-element count.  Pads hold ``sentinel_for(dtype)`` (the
dtype's maximum) so an ascending sort parks them at the tail and trimming by
count recovers the valid data.  For key-only sorts this is exact even when
real keys equal the sentinel (equal keys are indistinguishable).  For
key+payload sorts, pad entries are additionally forced *after* all real
entries by a secondary is-pad sort key, so no key value is reserved — unlike
the reference, which reserves ``-1`` on its wire for every job
(``server.c:405-406``, ``client.c:113``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sentinel_for(dtype) -> jnp.ndarray:
    """Largest representable value of ``dtype`` — the padding sentinel."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype=dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype=dtype)


def sort_keys(keys: jax.Array) -> jax.Array:
    """Ascending sort of a 1-D (or batched last-axis) key array.

    Key-only sorts are unstable (``is_stable=False``): equal keys are
    indistinguishable, and the unstable TPU sort network is ~40% faster at
    2^24 int32 keys (measured: 531 vs 374 Mkeys/s single-chip).  Key+payload
    sorts (`sort_kv` etc.) stay stable — there the order of equal keys is
    observable, and the reference's merge sort (``client.c:140-173``) is
    stable.
    """
    return jax.lax.sort((keys,), dimension=keys.ndim - 1, is_stable=False)[0]


def _apply_perm(payload: jax.Array, perm: jax.Array, axis: int) -> jax.Array:
    """Apply a per-slice sort permutation to a payload with trailing dims.

    Runs under the named scope ``payload_gather``, so the row gather is
    found by name in a device trace."""
    with jax.named_scope("payload_gather"):
        idx = perm.reshape(perm.shape + (1,) * (payload.ndim - perm.ndim))
        return jnp.take_along_axis(payload, idx, axis=axis)


def sort_kv(
    keys: jax.Array, payload: jax.Array, stable: bool = True
) -> tuple[jax.Array, jax.Array]:
    """Sort ``keys`` ascending, permuting ``payload`` rows along with them.

    ``payload`` has shape ``keys.shape + (...,)`` — e.g. TeraSort's 90-byte
    values as ``(n, 90)`` uint8.  Uses ``lax.sort``'s multi-operand form, so
    the permutation is applied on-chip in one fused op.

    ``stable=True`` (default) keeps equal-key payloads in input order, like
    the reference's stable merge sort (``client.c:140-173``).  Pass
    ``stable=False`` when any order of equal-key records is acceptable
    (e.g. TeraSort validity) — the unstable network is ~50% faster
    (measured 288 vs 190 Mrec/s at 2^22 int64+2 operands single-chip).
    """
    if payload.ndim == keys.ndim:
        out_k, out_v = jax.lax.sort(
            (keys, payload), dimension=-1, num_keys=1, is_stable=stable
        )
        return out_k, out_v
    # lax.sort wants equal-shaped operands; sort an index permutation instead.
    idx = jnp.broadcast_to(
        jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1), keys.shape
    )
    out_k, perm = jax.lax.sort(
        (keys, idx), dimension=-1, num_keys=1, is_stable=stable
    )
    return out_k, _apply_perm(payload, perm, keys.ndim - 1)


LOCAL_KERNELS = ("auto", "lax", "block", "bitonic", "pallas", "radix")

#: `auto` routes to the block kernel only above this length: below it the
#: whole sort fits ~one VMEM tile and lax.sort's fused path is already fine,
#: while the block kernel would pay padding + multi-kernel dispatch.
_AUTO_BLOCK_MIN = 1 << 16


def resolve_kernel(kernel: str, dtype, n: int, ndim: int = 1) -> str:
    """Resolve ``auto`` to a concrete kernel name for a given key shape.

    ``auto`` picks the block kernel on TPU for integer keys at sizes where it
    wins, ``lax`` otherwise (CPU/interpreter runs, float dtypes, small
    arrays).  Floats stay on lax: the comparator network's min/max would
    corrupt an order containing NaNs, and ``auto`` cannot know the array is
    NaN-free — framework float pipelines pre-map via ``ops.float_order`` to
    uints and so still reach the block kernel.
    """
    if kernel != "auto":
        return kernel
    from dsort_tpu.utils import backend

    dt = jnp.dtype(dtype)
    return (
        "block"
        if (
            ndim == 1
            and dt.itemsize in (4, 8)
            and not jnp.issubdtype(dt, jnp.floating)
            and n >= _AUTO_BLOCK_MIN
            and backend.on_tpu()
        )
        else "lax"
    )


def sort_with_kernel(keys: jax.Array, kernel: str = "auto") -> jax.Array:
    """Dispatch a 1-D ascending sort to one of the local kernel families.

    - ``auto`` (default): the block kernel on TPU for 32-bit keys at sizes
      where it wins; ``lax`` otherwise (CPU/interpreter runs, 64-bit keys,
      small arrays) — see `resolve_kernel`;
    - ``lax``: XLA's built-in sort (safe everywhere);
    - ``block``: the fused block-bitonic Pallas kernel (``ops.block_sort``) —
      the fastest single-chip kernel, with no 2^26 cliff (chip numbers:
      PERF.md);
    - ``bitonic``: the pure-jnp vectorized bitonic network (``ops.bitonic``);
    - ``pallas``: the Pallas VMEM tile-sort kernel (``ops.pallas_sort``);
    - ``radix``: the stable LSD counting-sort radix (``ops.radix``).
    """
    if kernel == "auto":
        kernel = resolve_kernel(kernel, keys.dtype, keys.shape[0], keys.ndim)
    if kernel == "lax":
        return sort_keys(keys)
    if kernel == "block":
        if jnp.issubdtype(keys.dtype, jnp.floating) and jnp.dtype(keys.dtype).itemsize == 8:
            return sort_keys(keys)  # f64 maps via float_order in the pipelines
        from dsort_tpu.ops.block_sort import block_sort

        return block_sort(keys)
    if kernel == "bitonic":
        from dsort_tpu.ops.bitonic import bitonic_sort

        return bitonic_sort(keys)
    if kernel == "pallas":
        from dsort_tpu.ops.pallas_sort import pallas_sort

        return pallas_sort(keys)
    if kernel == "radix":
        from dsort_tpu.ops.radix import radix_sort

        return radix_sort(keys)
    raise ValueError(f"unknown local kernel {kernel!r}; options: {LOCAL_KERNELS}")


def sort_padded(
    keys: jax.Array, count: jax.Array | int, kernel: str = "lax"
) -> tuple[jax.Array, jax.Array]:
    """Sort a fixed-size buffer whose first ``count`` entries are valid.

    Entries at positions >= ``count`` are overwritten with the sentinel before
    sorting, so the result is ``(sorted buffer with pads at the tail, count)``.
    """
    pos = jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1)
    masked = jnp.where(pos < count, keys, sentinel_for(keys.dtype))
    return sort_with_kernel(masked, kernel), jnp.asarray(count, jnp.int32)


def sort_kv2_padded(
    keys: jax.Array,
    secondary: jax.Array,
    payload: jax.Array,
    count: jax.Array | int,
    stable: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Two-level key+payload `sort_padded`: order is ``(key, secondary)``.

    For records whose sort key is wider than one machine word (TeraSort's
    10-byte keys: 8-byte ``keys`` prefix + 2-byte ``secondary`` tail), ties in
    the primary key are broken by ``secondary``.  Pads still sort after every
    real record — including real records whose (key, secondary) equals the
    sentinel pair — via the is-pad tiebreak, so no key value is reserved.
    Returns ``(keys, secondary, payload, count)``, all sorted together.
    """
    pos = jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1)
    is_pad = (pos >= count).astype(jnp.int8)
    masked = jnp.where(pos < count, keys, sentinel_for(keys.dtype))
    if payload.ndim == keys.ndim:
        out_k, _, out_s, out_v = jax.lax.sort(
            (masked, is_pad, secondary, payload),
            dimension=-1,
            num_keys=3,
            is_stable=stable,
        )
        return out_k, out_s, out_v, jnp.asarray(count, jnp.int32)
    idx = jnp.broadcast_to(
        jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1), keys.shape
    )
    out_k, _, out_s, perm = jax.lax.sort(
        (masked, is_pad, secondary, idx),
        dimension=-1,
        num_keys=3,
        is_stable=stable,
    )
    return (
        out_k,
        out_s,
        _apply_perm(payload, perm, keys.ndim - 1),
        jnp.asarray(count, jnp.int32),
    )


def sort_kv_padded(
    keys: jax.Array,
    payload: jax.Array,
    count: jax.Array | int,
    stable: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Key+payload variant of `sort_padded`, reserving no key value.

    Sorts lexicographically by ``(key, is_pad)`` so real entries whose key
    equals the sentinel still sort ahead of pads and keep their payloads.
    """
    pos = jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1)
    is_pad = (pos >= count).astype(jnp.int8)
    masked = jnp.where(pos < count, keys, sentinel_for(keys.dtype))
    if payload.ndim == keys.ndim:
        out_k, _, out_v = jax.lax.sort(
            (masked, is_pad, payload), dimension=-1, num_keys=2, is_stable=stable
        )
        return out_k, out_v, jnp.asarray(count, jnp.int32)
    idx = jnp.broadcast_to(
        jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1), keys.shape
    )
    out_k, _, perm = jax.lax.sort(
        (masked, is_pad, idx), dimension=-1, num_keys=2, is_stable=stable
    )
    return out_k, _apply_perm(payload, perm, keys.ndim - 1), jnp.asarray(count, jnp.int32)
