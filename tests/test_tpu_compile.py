"""Compile the main path's kernels for a described TPU v5e (no chip needed).

Lowering (``tests/test_lowering.py``) is not compiling: the TPU compiler
still refuses block tilings, VMEM and SMEM that lower cleanly. These tests
run the TPU compiler itself on ``ops.mttkrp_device_step`` with
``interpret=False``, at nell-2's published widths, R=16, ``tile_rows=8``
and the block size the runtime uses on the compiled path:

  * every mode compiles at the rung ``auto`` picks for it;
  * the out-of-core stream rung either compiles or is refused by the
    residency planner (its tile schedules are prefetched whole into SMEM).

The topology is described inside a fixture, never at import, so every
pytest worker collects the same tests and only the one that runs this file
loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.flycoo import choose_partition_params
from repro.core.tensors import FROSTT_PROFILES
from repro.kernels.mttkrp import ops as kops
from repro.oocore import planner

SHAPE = FROSTT_PROFILES["nell-2"]["shape"]
RANK = 16
TILE_ROWS = 8
NNZ_CAP = 1 << 20
BLK = kops.runtime_blk(choose_partition_params(SHAPE, NNZ_CAP, 1).g,
                       compiled=True)
# One worker owns every row: rows_cap is the mode size rounded to a tile.
ROWS = tuple(-(-d // TILE_ROWS) * TILE_ROWS for d in SHAPE)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _step_args(mode: int, nnz_cap: int, rows=ROWS):
    factor_rows = tuple(rows[w] for w in range(3) if w != mode)
    num_blocks = kops.n_pad_for(nnz_cap, rows[mode], BLK, TILE_ROWS) // BLK
    return factor_rows, num_blocks


def _compile(backend: str, mode: int, nnz_cap: int, sharding, rows=ROWS):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    factors = [sds((rows[w], RANK), jnp.float32) for w in range(3)]
    return kops.mttkrp_device_step.trace(
        sds((nnz_cap, 3), jnp.int32), sds((nnz_cap,), jnp.float32),
        sds((nnz_cap,), jnp.bool_), factors, mode=mode, rows_cap=rows[mode],
        row_offset=sds((), jnp.int32), blk=BLK, tile_rows=TILE_ROWS,
        interpret=False, backend=backend,
    ).lower(lowering_platforms=("tpu",)).compile()


def test_runtime_blk_is_mosaic_legal():
    assert BLK % kops._kernel.COMPILED_BLK_MULTIPLE == 0


@pytest.mark.parametrize("mode", range(3))
def test_auto_rung_compiles(mode, one_chip, no_persistent_cache):
    factor_rows, num_blocks = _step_args(mode, NNZ_CAP)
    backend = kops.select_backend(
        "auto", nmodes=3, rank=RANK, blk=BLK, tile_rows=TILE_ROWS,
        factor_rows=factor_rows, num_blocks=num_blocks)
    assert backend.startswith("pallas"), backend
    compiled = _compile(backend, mode, NNZ_CAP, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("rows,nnz_cap,admitted", [
    # nell-2: its schedules (one row per block, and every 8-row output
    # tile opens a block) outgrow SMEM long before its real nnz.
    (ROWS, NNZ_CAP, False),
    # 128 output tiles: schedules fit, so the rung must compile.
    ((1024, 2048, 4096), 1 << 13, True),
], ids=["nell-2", "small"])
def test_stream_rung_compiles_or_is_refused(rows, nnz_cap, admitted,
                                            one_chip, no_persistent_cache):
    factor_rows, num_blocks = _step_args(0, nnz_cap, rows)
    fits = planner.backend_fits(
        kops.STREAM_BACKEND, nmodes=3, rank=RANK, blk=BLK,
        tile_rows=TILE_ROWS, factor_rows=factor_rows, num_blocks=num_blocks)
    assert fits == admitted
    if fits:
        compiled = _compile(kops.STREAM_BACKEND, 0, nnz_cap, one_chip, rows)
        assert "tpu_custom_call" in compiled.as_text()


# A 5-mode mode step on the row-gathering rung, at vast-2015-mc1-5d's
# published widths: output mode 4 (89 rows, 12 tiles of 8), whose four
# input factors include mode 0's 165,427 rows, which no VMEM holds.
VAST = (165427, 11374, 2, 100, 89)
VAST_ROWS = tuple(-(-d // TILE_ROWS) * TILE_ROWS for d in VAST)
# Temp bytes a nonzero may take. Four input factor rows each padded to 128
# fp32 lanes take 4 × 512 = 2048 B a nonzero before the kernel reads one;
# the rung keeps one rank-major Khatri-Rao row instead (64 B at R=16),
# beside the stream's aligned scalars and the chunked gather's fixed
# (KRP_CHUNK_SLOTS · 4 · 512 B, ~0.5 GB) working set.
VAST_TEMP_PER_NNZ = 1024


def test_vast_fused_step_temp_per_nonzero(one_chip, no_persistent_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    mode = 4
    compiled = kops.mttkrp_device_step.trace(
        sds((NNZ_CAP, 5), jnp.int32), sds((NNZ_CAP,), jnp.float32),
        sds((NNZ_CAP,), jnp.bool_),
        [sds((r, RANK), jnp.float32) for r in VAST_ROWS], mode=mode,
        rows_cap=VAST_ROWS[mode], row_offset=sds((), jnp.int32), blk=BLK,
        tile_rows=TILE_ROWS, interpret=False, backend="pallas_fused",
    ).lower(lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < VAST_TEMP_PER_NNZ * NNZ_CAP, temp / NNZ_CAP
