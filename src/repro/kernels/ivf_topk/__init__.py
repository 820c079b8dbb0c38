from repro.kernels.ivf_topk.ops import scan_topk_probed, scan_topk_quantized
