"""The resident fold compiled for a described TPU v5e, at the shapes the
benchmark's cells run it: no chip is attached and nothing runs, but the
chip's own compiler says what the program is made of (the
on-chip-measurement guide, section 2). All in this one file, the
topology described inside a fixture: one process may load the TPU's
library, and under several workers only the one handed this file does.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from opentsdb_tpu.ops import kernels

SLOTS, BLOCK, SERIES = 1 << 21, 1 << 16, 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compiled_fold(one_chip, agg, buckets, interval):
    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    nseg = SERIES * buckets + 1
    group = kernels._FOLD_GROUP
    chunk = (of((SLOTS,), jnp.int32), of((SLOTS,), jnp.float32),
             of((SLOTS,), jnp.int32), of((SLOTS,), jnp.bool_))
    return kernels._chunk_fold.lower(
        (chunk,) * group, *[of((nseg,), jnp.float32)] * 5,
        of((), jnp.int32),
        of((4 + 2 * group * (SLOTS // BLOCK),), jnp.int32),
        num_series=SERIES,
        num_buckets=buckets, interval=interval, need=kernels._needs(agg),
        block=BLOCK).compile().as_text()


def written(text):
    """(operation, element count) of every array an instruction outside
    a fusion's body produces: what the program writes to memory."""
    out, fused = [], False
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(", line)
        if head:
            fused = "fused_computation" in head.group(2)
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([\w\-]+)\(", line)
        if m and not fused:
            for dims in re.findall(r"\[([\d,]+)\]", m.group(1)):
                n = 1
                for d in dims.split(","):
                    n *= int(d)
                out.append((m.group(2), n))
    return out


@pytest.mark.parametrize("agg,buckets,interval", [
    ("max", 16, 3600), ("avg", 16, 3600), ("max", 1024, 60),
    ("dev", 16, 3600)])
def test_a_turn_of_the_run_reduction_stays_in_its_fusions(one_chip, agg,
                                                          buckets, interval):
    text = compiled_fold(one_chip, agg, buckets, interval)
    # The compare / select / reduce of a turn fuse: nothing of the size
    # of [tiles, tile, runs] is written, only [tiles, runs] results, the
    # block's columns, the chunk's and the accumulators.
    cube = BLOCK * kernels._FOLD_RUNS
    nseg = SERIES * buckets + 1
    big = [(op, n) for op, n in written(text)
           if n >= cube and n not in (SLOTS, nseg)]
    assert not big, big
    # One program, its trip counts data: loops, and no branch.
    assert " while(" in text and " conditional(" not in text
    assert 'op_name="jit(_chunk_fold)/window.chunk_fold/' in text


# The device block cache's programs (compress/kernels.py) at the shapes
# tsbs-cpu-13h-tsst4 runs them: of its 4,421 blocks of 42,480 points
# the 1,560 its budget of 1 << 26 points has rows of 43,008 for, 118
# records a block in 128, a fill of 8 blocks.
ROWS, P_BLK, R_BLK, FILL = (1 << 26) // 43008, 43008, 128, 8
SLAB_BYTES = ROWS * P_BLK * 4


def slab_shapes(one_chip):
    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    scalars = [of((), jnp.int32)] * 3 + [of((), jnp.float32)] * 2
    return of, [of((ROWS, P_BLK), dt)
                for dt in (jnp.int32, jnp.float32)], scalars


@pytest.mark.parametrize("vkind", ["f32", "int"])
def test_a_fill_decodes_into_the_slabs_in_place(one_chip, vkind):
    from opentsdb_tpu.compress import kernels as ckernels
    of, slabs, _ = slab_shapes(one_chip)
    compiled = ckernels.slab_fill.lower(
        *slabs, of((FILL,), jnp.int32),
        of((FILL, P_BLK // 2), jnp.uint8), of((FILL, P_BLK * 4), jnp.uint8),
        of((FILL, P_BLK // 2), jnp.uint8), of((FILL, P_BLK * 4), jnp.uint8),
        of((FILL, R_BLK), jnp.int32), vkind=vkind).compile()
    # The two slabs are donated and come back as the outputs: no
    # second copy of them, and a fill's own arrays are a few blocks'.
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * SLAB_BYTES
    assert mem.temp_size_in_bytes < SLAB_BYTES // 8


def test_the_selective_stage_reads_the_slabs_where_they_lie(one_chip):
    """A one-host request stages 8,192 points: it gathers them out of
    the slabs by row and column and copies no slab (flattened, the
    compiler relays one out, 870 MB a sub-query)."""
    from opentsdb_tpu.compress import kernels as ckernels
    of, slabs, scalars = slab_shapes(one_chip)
    m = 8192
    compiled = ckernels.slab_stage_sel.lower(
        *slabs, *[of((m,), jnp.int32)] * 4,
        of((m,), jnp.bool_), *scalars, num_series=16, num_buckets=256,
        interval=300, agg_down="max").compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 24


def test_the_dense_stage_compiles_at_the_fleet_wide_gather(one_chip):
    """Every host of a metric over 13 row-hours: 425 blocks in 512
    rows, 22M points into a 4,096 x 16 grid beside the slabs."""
    from opentsdb_tpu.compress import kernels as ckernels
    of, slabs, scalars = slab_shapes(one_chip)
    k = 512
    compiled = ckernels.slab_stage_rows.lower(
        *slabs, of((k,), jnp.int32), *[of((k, R_BLK), jnp.int32)] * 3,
        of((k, R_BLK), jnp.bool_), *scalars, num_series=SERIES,
        num_buckets=16, interval=3600, agg_down="avg").compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


# The raw plan's fleet-wide program at the shape cpu4k-13h.hist-12h runs
# it: 12 h of 4,000 hosts are 17.3M points in a stream of 20.97M slots
# (5 << 22, the quarter-octave ladder), 320 blocks of kernels._STAGE_BLOCK.
STREAM = 5 << 22


@pytest.mark.parametrize("agg", ["avg", "max", "dev"])
def test_the_raw_stage_takes_its_stream_a_block_a_turn(one_chip, agg):
    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = kernels.downsample_multigroup.lower(
        of((STREAM,), jnp.int32), of((STREAM,), jnp.float32),
        of((STREAM,), jnp.int32), of((STREAM,), jnp.bool_),
        of((SERIES,), jnp.int32), num_series=SERIES, num_groups=SERIES,
        num_buckets=16, interval=3600, agg_down=agg,
        agg_group=agg).compile().as_text()
    # As the fold's turn: nothing of [tile, runs, tiles] is written.
    # What is as large or larger is a column of the stream or a part of
    # one, the runs' left folds among them (kernels._run_fold, which
    # pads the stream to whole columns of its own).
    cube = kernels._STAGE_BLOCK * kernels._FOLD_RUNS
    big = [(op, n) for op, n in written(text)
           if n == cube
           or n > STREAM + kernels._STAGE_FOLD * kernels._LANES]
    assert not big, big
    # Its trip counts are data: loops, and no branch.
    assert " while(" in text and " conditional(" not in text
