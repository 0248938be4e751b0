"""Multi-chip / multi-shard collectives (DESIGN.md §6, §14).

Restored in two stages.  The REDUCE plane is real in this build:

  * :func:`tree_reduce` — deterministic host-local binary-tree reduction.
    The sharded store plane (``repro.core.shard``) routes its
    scatter-gather ``ScanResult`` merge through it, so merged results
    have a FIXED association order regardless of shard completion order
    (integers merge associatively either way; the fixed tree makes any
    float accumulation reproducible too).
  * :func:`compressed_allreduce` — int8-compressed SUM all-reduce of a
    pytree over one mesh axis (``shard_map`` + ``psum``): each device
    quantizes to int8 with a per-leaf scale, sums in int32 over the axis,
    and dequantizes.  With replicated inputs over an axis of size *n* the
    result is ``n * value`` up to quantization error — exactly what the
    multi-device subprocess test pins.

The flash-decoding sharded attention path has NOT been restored yet
(``ATTENTION_IS_STUB``): its body still raises, every single-device path
guards on mesh shape before reaching it
(``transformer._use_sharded_decode``), and the attention-dependent
subprocess tests stay skip-marked on :data:`IS_STUB`.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence, TypeVar

T = TypeVar("T")

# the reduce plane (tree_reduce, compressed_allreduce) is implemented
REDUCE_IS_STUB = False
# the shard_map flash-decoding attention path is still a documented stub
ATTENTION_IS_STUB = True
# back-compat gate for the model-parallel subprocess tests: those paths
# end in the sharded attention kernel, so they skip while it is stubbed
IS_STUB = ATTENTION_IS_STUB

_MSG = ("repro.dist.collectives is a minimal shim in this build; the "
        "multi-device {name} path has not been restored yet")


def tree_reduce(items: Sequence[T], fn: Callable[[T, T], T]) -> T:
    """Reduce ``items`` with a deterministic binary tree.

    Association order is fixed by position — ``((x0·x1)·(x2·x3))…`` with
    an odd trailing element carried up unchanged — and never by arrival
    or completion order.  This is the host-local form of the pairwise
    reduction a pod-axis all-reduce performs; the shard scan merge uses
    it so N-shard results are bit-reproducible run to run.
    """
    xs = list(items)
    if not xs:
        raise ValueError("tree_reduce needs >= 1 item")
    while len(xs) > 1:
        nxt = []
        for i in range(0, len(xs) - 1, 2):
            nxt.append(fn(xs[i], xs[i + 1]))
        if len(xs) % 2:
            nxt.append(xs[-1])
        xs = nxt
    return xs[0]


def _quantized_psum(x, axis: str):
    """One leaf of :func:`compressed_allreduce`: int8 quantize -> int32
    psum -> dequantize.  Must run inside a shard_map/pmap over ``axis``.

    The quantization scale is AGREED over ``axis`` first (scalar pmax of
    the per-device amax): dequantizing the summed int32 payload with a
    device-LOCAL scale is silently wrong the moment inputs differ across
    the axis — and gradients, the payload this exists for, always do.
    """
    import jax.numpy as jnp
    from jax import lax

    x = jnp.asarray(x)
    out_dtype = (x.dtype if jnp.issubdtype(x.dtype, jnp.floating)
                 else jnp.float32)
    xf = x.astype(jnp.float32)
    amax = lax.pmax(jnp.max(jnp.abs(xf)), axis)
    scale = jnp.where(amax > 0, amax, jnp.float32(1.0)) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    s = lax.psum(q.astype(jnp.int32), axis)
    return (s.astype(jnp.float32) * scale).astype(out_dtype)


def compressed_allreduce(tree: Any, mesh, axis: str = "pod") -> Any:
    """int8-compressed SUM all-reduce of a pytree over mesh ``axis``.

    Per leaf (:func:`_quantized_psum`): the per-device ``max|x|`` is
    pmax-agreed over ``axis``, values quantize to int8 with the shared
    scale ``amax / 127``, the int8 payload psums in int32, and the sum
    dequantizes with the same shared scale.  Wire cost is 1/4 of an f32
    all-reduce; the error per element is bounded by
    ``n_axis * scale / 2``.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    f = jax.shard_map(
        lambda t: jax.tree.map(lambda x: _quantized_psum(x, axis), t),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)
    return f(tree)


def sharded_decode_attention_gqa(q, k, v, pos, mesh=None, *, window: int = 0,
                                 q_position=None, batch_axes=("data",),
                                 seq_axis: str = "model"):
    """Flash-decoding GQA with the KV sequence sharded over ``seq_axis``."""
    raise NotImplementedError(_MSG.format(name="sharded_decode_attention_gqa"))
