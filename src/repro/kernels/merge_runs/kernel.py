"""Pallas TPU bitonic merge of two sorted runs (LSM compaction hot loop).

Hardware adaptation: the paper's compaction merge is a pointer-walking
two-finger merge — branchy, scalar, hostile to TPU vector units.  The
TPU-native equivalent: concatenate run A (ascending) with run B *reversed*
(descending) to form a bitonic sequence of length 2T, then run the
log2(2T)-stage bitonic **merge network**.  The wrapper reverses B and
concatenates in XLA, so the kernel sees one (BG, 2T) block.  Every stage is a
compare-exchange with the partner lane ``i XOR stride``: two lane rotations
(``pltpu.roll``) fetch both neighbours, an iota-parity select picks the
partner, and an element-wise min/max keeps the lower key in the lower lane.
No gathers, no data-dependent control flow.  Payloads co-move via select on
the key comparison.

Grid: one program per row-group of tiles; each program holds its
(BG, 2T) working set in VMEM.  T must be a power of two; keys
int32/float32, payload any 32-bit dtype.  This kernel merges single 32-bit
keys and is not on the store's path (``ops.merge_order`` is).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _merge_kernel(k_ref, v_ref, ok_ref, ov_ref, *, width: int):
    keys = k_ref[...]
    vals = v_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    stride = width // 2
    while stride >= 1:
        # the rotation that brings lane i^stride to lane i, whichever way
        # roll turns: compare the rotated lane ids against the partner ids
        fwd = pltpu.roll(lane, stride, 1) == (lane ^ stride)
        pk = jnp.where(fwd, pltpu.roll(keys, stride, 1), pltpu.roll(keys, width - stride, 1))
        pv = jnp.where(fwd, pltpu.roll(vals, stride, 1), pltpu.roll(vals, width - stride, 1))
        lower = (lane & stride) == 0
        take = (lower & (keys > pk)) | (~lower & (pk > keys))
        keys = jnp.where(take, pk, keys)
        vals = jnp.where(take, pv, vals)
        stride //= 2
    ok_ref[...] = keys
    ov_ref[...] = vals


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def merge_runs_pallas(
    a_keys: jax.Array,  # (G, T) ascending rows, T a power of two
    b_keys: jax.Array,
    a_vals: jax.Array,
    b_vals: jax.Array,
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    g, t = a_keys.shape
    assert t & (t - 1) == 0, f"tile width must be a power of two, got {t}"
    bg = min(block_rows, g)
    assert g % bg == 0, (g, bg)
    # [A ascending | B descending] is bitonic
    keys = jnp.concatenate([a_keys, jnp.flip(b_keys, 1)], axis=1)
    vals = jnp.concatenate([a_vals, jnp.flip(b_vals, 1)], axis=1)
    spec = pl.BlockSpec((bg, 2 * t), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_merge_kernel, width=2 * t),
        grid=(g // bg,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((g, 2 * t), a_keys.dtype),
            jax.ShapeDtypeStruct((g, 2 * t), a_vals.dtype),
        ],
        interpret=interpret,
    )(keys, vals)
