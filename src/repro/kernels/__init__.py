"""Pallas TPU kernels. On a TPU the ops dispatch to the compiled kernel;
on the CPU the tests run each kernel with interpret=True against its
ref.py oracle, and tests/test_tpu_compile.py compiles them for a
described v5e chip.

l2_topk       — fused distance + online top-k scan (the retrieval hot path)
rae_encode    — RAE encoder GEMM + fused L2-normalize epilogue
flash_decode  — split-KV online-softmax decode attention
embedding_bag — scalar-prefetch gather-reduce (torch EmbeddingBag on TPU)
pq_adc        — fused PQ ADC scan: LUT build + one-hot code gather + top-k
graph_beam    — fused neighbor gather + L2 + beam merge (one batched HNSW hop)
graph_beam_q  — the quantized hop: SQ8/PQ code gather + asymmetric score + merge
topk_merge    — deterministic scatter-gather top-k merge (sharded search)
"""
from .common import NEG_INF, PAD_ID, PAD_PENALTY, canonicalize_pads
from .embedding_bag.ops import embedding_bag
from .flash_decode.ops import flash_decode
from .graph_beam.ops import graph_beam
from .graph_beam_q.ops import graph_beam_q
from .l2_topk.ops import l2_topk
from .pq_adc.ops import pq_adc
from .rae_encode.ops import rae_encode
from .topk_merge.ops import topk_merge

__all__ = ["NEG_INF", "PAD_ID", "PAD_PENALTY", "canonicalize_pads",
           "embedding_bag", "flash_decode", "graph_beam", "graph_beam_q",
           "l2_topk", "pq_adc", "rae_encode", "topk_merge"]
