"""Kernel micro-benchmarks (interpret-mode wall time is NOT TPU-predictive;
the derived column carries the analytic bytes/flops that the roofline uses —
the comparison of interest on CPU is kernel-vs-oracle agreement + the scan's
arithmetic-intensity accounting).

``ivf_probe_*`` is the exception: it times the two *production* probe paths
of ``ivf.search`` against each other on identical shapes — the fused-kernel
scan of the slab in place (int8 end-to-end, each probed partition read once
per call) vs the legacy fp32 gather-dequant einsum. The kernel path never
materialises the (qb, P, cap, d) fp32 dequant nor any copy of the probed
rows, and replaces the full-width top-k with a chunk-survivor top-k + tiny
rescore."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timeit
from repro.core import ivf as ivf_mod
from repro.core import partitioner
from repro.core.quantization import quantize
from repro.kernels.ivf_topk.ops import scan_topk_quantized
from repro.kernels.ivf_topk.ref import scan_topk_ref, topk_from_chunks
from repro.kernels.segment_reduce.ops import segment_sum_mm
from repro.kernels.segment_reduce.ref import segment_sum_ref
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref


def run(report):
    rng = np.random.default_rng(0)

    # ivf probe path: fused kernel vs fp32-gather einsum on the same shapes
    n, d, nq, n_probe, k = 8192, 128, 64, 8, 10
    v = rng.normal(size=(n, d)).astype(np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    idx, _ = ivf_mod.build(jax.random.PRNGKey(0), jnp.asarray(v),
                           jnp.arange(n), n_partitions=32, bits=8)
    q = jnp.asarray(v[:nq] + 0.02 * rng.normal(size=(nq, d)).astype(np.float32))
    t_e = timeit(lambda: ivf_mod.search(idx, q, n_probe=n_probe, k=k,
                                        impl="einsum"), trials=3)
    t_k = timeit(lambda: ivf_mod.search(idx, q, n_probe=n_probe, k=k,
                                        impl="kernel"), trials=3)
    m_rows = n_probe * idx.capacity
    fp32_interm = nq * m_rows * d * 4          # the einsum path's HBM dequant
    probes, _ = partitioner.assign_topk(q, idx.centroids, n_probe)
    slab_read = len(np.unique(np.asarray(probes))) * idx.capacity * d
    report("ivf_probe_einsum", t_e * 1e6,
           f"fp32_dequant_bytes={fp32_interm:.2e}")
    report("ivf_probe_kernel", t_k * 1e6,
           f"speedup={t_e / t_k:.2f}x fp32_dequant_bytes=0 "
           f"int8_slab_bytes_read={slab_read:.2e}")

    # ivf_topk: HBM bytes per query at int8 vs bf16 storage
    n, d, q = 8192, 128, 64
    v = rng.normal(size=(n, d)).astype(np.float32)
    qv = quantize(jnp.asarray(v), 8)
    queries = jnp.asarray(v[:q])
    valid = jnp.ones((n,), bool)
    t_k = timeit(lambda: scan_topk_quantized(queries, qv.data, qv.vmin[:, 0],
                                             qv.scale[:, 0], valid, k=10),
                 trials=3)
    int8_bytes = n * d
    bf16_bytes = n * d * 2
    report("k_ivf_topk_int8", t_k / q * 1e6,
           f"hbm_bytes_per_scan={int8_bytes} vs_bf16={bf16_bytes} (2x saved)")

    # segment_reduce: one-hot-matmul MXU formulation
    e, dd, nn = 8192, 64, 1024
    msg = jnp.asarray(rng.normal(size=(e, dd)).astype(np.float32))
    seg = jnp.asarray(rng.integers(0, nn, e).astype(np.int32))
    t_k = timeit(lambda: segment_sum_mm(msg, seg, nn), trials=3)
    t_r = timeit(lambda: segment_sum_ref(msg, seg, nn), trials=3)
    mxu_flops = 2 * e * nn * dd   # the one-hot matmul the TPU would run
    report("k_segment_reduce", t_k * 1e6,
           f"ref_us={t_r*1e6:.0f} mxu_flops={mxu_flops:.2e}")

    # decode_attention: flash-decode bytes per token
    b, hkv, g, hd, s = 4, 8, 8, 128, 4096
    qa = jnp.asarray(rng.normal(size=(b, hkv * g, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)).astype(np.float32))
    vv = jnp.asarray(rng.normal(size=(b, s, hkv, hd)).astype(np.float32))
    valid = jnp.ones((b, s), bool)
    t_k = timeit(lambda: decode_attention(qa, k, vv, valid), trials=3)
    kv_bytes = 2 * b * s * hkv * hd * 4
    report("k_decode_attention", t_k * 1e6,
           f"kv_bytes={kv_bytes:.2e} tokens={b}")
