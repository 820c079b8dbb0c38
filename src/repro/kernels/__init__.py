"""Pallas TPU kernels for HMGI's compute hot spots.

Each kernel package has: <name>.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd public wrapper), ref.py (pure-jnp oracle). Off-TPU the wrappers
run the same kernel bodies under ``interpret=True`` — each package probes the
backend once, lazily on the first kernel call (``_interpret_mode``, cached),
so CPU CI and laptops execute the identical code path the TPU compiles while
app-level JAX setup (``jax.distributed.initialize``) still runs first.

  ivf_topk         — fused int8-dequant scan + per-chunk partial top-1
                     (the paper's ANNS hot loop; ScaNN-on-TPU layout).
                     Two entry points: ``scan_topk_quantized`` scans one
                     corpus slab shared by all queries (delta store,
                     monolithic baseline); ``scan_topk_probed`` is the IVF
                     probe path — it reads the (K, cap, d) index slab in
                     place, partition-major, each probed partition once per
                     call against every query that probes it, and rescores
                     each query's top-k chunk survivors exactly. int8 rows
                     never dequantize to fp32 in HBM on either path.
  segment_reduce   — one-hot-matmul segment sum (GNN message passing,
                     EmbeddingBag reduce; MXU-friendly scatter replacement)
  decode_attention — GQA single-token flash-decode with online softmax
                     (serving hot loop for the RAG engine)

Benchmarks: ``benchmarks/kernels_bench.py`` times the kernel-backed probe
path against the legacy fp32 gather-dequant einsum on identical shapes;
``benchmarks/hybrid_bench.py`` covers the downstream candidate-sparse fusion
stage.
"""
