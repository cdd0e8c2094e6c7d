"""Ahead-of-time compiles of the main path's kernels for a described TPU
v5e, at wiki100k shapes (the chip smoke's corpus: 100,000 docs, a
200,000-word vocabulary, 50,000-doc streaming batches). Nothing runs:
this is what the chip's compiler would refuse — tiling, VMEM, programs
that do not fit the device — found at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker
imports this file (see the on-chip-measurement guide, section 2)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_ir.index.blockmax import num_blocks
from tpu_ir.ops import build_postings_jit
from tpu_ir.ops.pallas_scoring import (
    pallas_tfidf_scores,
    pallas_tfidf_scores_quantized,
)
from tpu_ir.ops.postings import round_cap
from tpu_ir.ops.scoring import (
    ColdChunks,
    blockmax_cand_blocks,
    bm25_topk_blockmax,
    bm25_topk_tiered,
    tfidf_topk_tiered,
)
from tpu_ir.search.layout import COLD_CHUNK

N_DOCS = 100_000
VOCAB = 200_000
# the tiered layout Scorer.load built for that corpus on the chip
# (chip_smoke.py's "layout:" line, PR 21): hot strip rows = the df > p99
# terms, then one [terms, capacity] tier per df rung
HOT = 2_866
TIERS = ((100_001, 2), (1_945, 8), (103_578, 32), (59_752, 128),
         (16_100, 512), (2_354, 2_048))
BLOCK_W = 512
BATCH_OCC = round_cap(50_000 * 320)  # one streaming batch's occurrences
QUERIES = 1_024  # a query block
TERMS = 8


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache (the read warns, then compiles again): keep the
    # cache off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def tiered_operands(s):
    i32 = jnp.int32
    return (spec((QUERIES, TERMS), i32, s),            # q_terms
            spec((VOCAB,), i32, s),                    # hot_rank
            spec((HOT, N_DOCS + 1), jnp.float32, s),   # hot_tfs
            spec((VOCAB,), i32, s),                    # tier_of
            spec((VOCAB,), i32, s),                    # row_of
            tuple(spec(t, i32, s) for t in TIERS),     # tier_docs
            tuple(spec(t, i32, s) for t in TIERS),     # tier_tfs
            spec((VOCAB,), i32, s))                    # df


def fits_one_chip(compiled) -> bool:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 << 30


def test_build_postings_streaming_batch(chip):
    lowered = build_postings_jit.lower(
        spec((BATCH_OCC,), jnp.int32, chip),
        spec((BATCH_OCC,), jnp.int32, chip),
        vocab_size=VOCAB, num_docs=N_DOCS)
    assert fits_one_chip(lowered.compile())


def test_tfidf_topk_tiered(chip):
    ops = tiered_operands(chip)
    compiled = tfidf_topk_tiered.lower(
        *ops, spec((), jnp.int32, chip), num_docs=N_DOCS, k=10).compile()
    assert fits_one_chip(compiled)
    # the hot-strip matmul keeps full f32 on the chip: at default
    # precision it is one bf16 pass, far outside exact-score parity
    assert "operand_precision={highest,highest}" in compiled.as_text()


def test_bm25_topk_tiered_deep_k(chip):
    ops = tiered_operands(chip)
    lowered = bm25_topk_tiered.lower(
        *ops, spec((N_DOCS + 1,), jnp.int32, chip),
        spec((), jnp.int32, chip), num_docs=N_DOCS, k=1000)
    assert fits_one_chip(lowered.compile())


def test_bm25_topk_blockmax(chip):
    ops = tiered_operands(chip)
    nblk = num_blocks(N_DOCS, BLOCK_W)
    lowered = bm25_topk_blockmax.lower(
        *ops, spec((N_DOCS + 1,), jnp.int32, chip),
        spec((), jnp.int32, chip), spec((HOT, nblk), jnp.float32, chip),
        num_docs=N_DOCS, width=BLOCK_W,
        cand_blocks=blockmax_cand_blocks(10, N_DOCS, BLOCK_W), k=10)
    assert fits_one_chip(lowered.compile())


def test_bm25_topk_blockmax_chunk_stream(chip):
    """The production deep-k kernel with the 2,048-slot tier streamed as
    chunks, at the capacity a block of 4,096 chunks is dispatched at."""
    ops = tiered_operands(chip)
    nblk = num_blocks(N_DOCS, BLOCK_W)
    rows = sum(v * c // COLD_CHUNK for v, c in TIERS if c % COLD_CHUNK == 0)
    chunks = ColdChunks(spec((rows, COLD_CHUNK), jnp.int32, chip),
                        spec((rows, COLD_CHUNK), jnp.int32, chip),
                        spec((VOCAB,), jnp.int32, chip),
                        spec((VOCAB,), jnp.int32, chip))
    lowered = bm25_topk_blockmax.lower(
        *ops, spec((N_DOCS + 1,), jnp.int32, chip),
        spec((), jnp.int32, chip), spec((HOT, nblk), jnp.float32, chip),
        num_docs=N_DOCS, width=BLOCK_W,
        cand_blocks=blockmax_cand_blocks(1000, N_DOCS, BLOCK_W), k=1000,
        chunks=chunks, n_chunks=4096)
    assert fits_one_chip(lowered.compile())


@pytest.mark.parametrize("kernel,strip_dtype", [
    (pallas_tfidf_scores, jnp.float32),
    (pallas_tfidf_scores_quantized, jnp.bfloat16),
])
def test_pallas_kernels(chip, kernel, strip_dtype):
    # the dense layout's widths: a [V, D+1] strip the kernel row-gathers
    v, d = 4_096, 8_192
    lowered = kernel.lower(
        spec((64, TERMS), jnp.int32, chip), spec((v, d), strip_dtype, chip),
        spec((v,), jnp.int32, chip), spec((), jnp.int32, chip))
    assert "tpu_custom_call" in lowered.compile().as_text()
