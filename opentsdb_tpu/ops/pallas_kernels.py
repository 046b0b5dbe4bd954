"""Pallas TPU kernel for segment reductions.

The query hot loop (ops/kernels.py downsample_group) is a pair of segment
reductions over a flat point stream — the vectorized replacement for the
reference's pull-iterator stack (SpanGroup.SGIterator,
Span.DownsamplingIterator; reference src/core/SpanGroup.java:370-796).

``pallas_segment_sum`` implements the reduction as an MXU one-hot matmul:
a [C]-point chunk scatter-adds into [T] segment bins as
``one_hot(seg)ᵀ @ features`` — systolic-array work with zero dynamic
indexing. It streams point chunks through VMEM with a 2-D grid
(segment-tile × chunk); each output tile stays resident in VMEM while all
chunks accumulate into it, so HBM traffic is one read of the points per
segment tile plus one write of the bins.

The production kernels do not call it: they issue one rank-1 XLA
``jax.ops.segment_sum`` per needed statistic (ops/kernels.py
_segment_moments). How the one-hot matmul, the rank-1 scatter, a
feature-stacked [N, K] scatter and segment_min/max compare on a local
chip is not measured (PERF.md, open questions); the kernel is kept as
a validated alternative and the interpret-mode semantics oracle for
tests.

``segment_sum_features`` remains the stacked-API entry point for callers
that want K features reduced together; it unstacks into rank-1 XLA
segment_sums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Chunk of points processed per grid step; segment-bin tile held in VMEM.
# [CHUNK, SEG_TILE] one-hot (f32) = 2 MB of VMEM — well under the ~16 MB
# budget with double buffering. CHUNK is 1024 because XLA lays out 1-D
# int32 operands with a 1024-element tile and Mosaic requires the block
# to match it.
CHUNK = 1024
SEG_TILE = 512


def _seg_sum_kernel(seg_ref, feat_ref, out_ref):
    """One (segment-tile i, chunk j) cell: accumulate this chunk's
    contribution to segment bins [i*SEG_TILE, (i+1)*SEG_TILE)."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    seg = seg_ref[:]                          # [CHUNK] int32
    local = seg - i * SEG_TILE                # position within this tile
    cols = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, SEG_TILE), 1)
    onehot = (local[:, None] == cols).astype(jnp.float32)  # [CHUNK, SEG_TILE]
    # Scatter-as-matmul on the MXU: binsᵀ += one_hotᵀ @ features.
    # HIGHEST precision: the default lowers f32 matmuls to bf16 MXU
    # passes, which loses ~3 mantissa digits — caught by the hardware
    # parity test (interpret mode computes in full f32 and never sees it).
    out_ref[:] += jnp.dot(onehot.T, feat_ref[:],
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def pallas_segment_sum(feat: jnp.ndarray, seg: jnp.ndarray,
                       num_segments: int, *, interpret: bool = False):
    """Segment-sum [N, K] features by [N] segment ids → [num_segments, K].

    Out-of-range ids (e.g. the padding trash segment) drop out naturally:
    their one-hot row is all-zero in every tile. N pads up to CHUNK and
    num_segments up to SEG_TILE internally; K should be small (a feature
    stack like [valid, value, rel_ts], not a wide matrix).
    """
    n, k = feat.shape
    n_pad = -n % CHUNK
    if n_pad:
        feat = jnp.pad(feat, ((0, n_pad), (0, 0)))
        seg = jnp.pad(seg, (0, n_pad), constant_values=-1)
    n_chunks = (n + n_pad) // CHUNK
    t_pad = -num_segments % SEG_TILE
    nseg_pad = num_segments + t_pad
    n_tiles = nseg_pad // SEG_TILE

    # Under shard_map the out_shape needs the inputs' varying-manual-axes
    # set, or tracing rejects the pallas_call (check_vma).
    out_shape = jax.ShapeDtypeStruct((nseg_pad, k), jnp.float32,
                                     vma=jax.typeof(feat).vma)
    out = pl.pallas_call(
        _seg_sum_kernel,
        grid=(n_tiles, n_chunks),
        in_specs=[
            # 1-D chunk of ids (last dim CHUNK % 128 == 0) and a
            # [CHUNK, k] feature block (full last dim, CHUNK % 8 == 0) —
            # the Mosaic tiling rules for VMEM blocks.
            pl.BlockSpec((CHUNK,), lambda i, j: (j,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((CHUNK, k), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((SEG_TILE, k), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_shape,
        interpret=interpret,
    )(seg, feat)
    return out[:num_segments]


# Retained for callers that tune dispatch: the one-hot matmul's FLOPs
# grow with nseg_pad, so above this count it cannot win. The default
# path does not consult it.
PALLAS_MAX_SEGMENTS = 4096


def segment_sum_features(feat: jnp.ndarray, seg: jnp.ndarray,
                         num_segments: int):
    """Segment-sum K stacked features: K rank-1 XLA segment_sums.

    Semantics are identical to
    ``jax.ops.segment_sum(feat, seg, num_segments)``.
    """
    return jnp.stack(
        [jax.ops.segment_sum(feat[:, i], seg, num_segments)
         for i in range(feat.shape[1])], axis=1)
