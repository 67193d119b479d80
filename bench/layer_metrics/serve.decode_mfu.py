"""Whole decode tick's share of the chip's peak.

The least time of a tick is the larger of its required FLOPs over peak
FLOP/s and its required bytes over HBM bandwidth.  Required bytes: every
weight the step reads once at the configuration's bf16 (the layers, the
final norm and the head; the embedding gives one row a slot), plus the KV
rows of the live requests, K and V in bf16.  Required FLOPs: two a weight
for each decoding slot, plus 4 * rows * heads * head_dim a layer for
attention.  Decode is bound by bytes.  The measured time of a tick is the
traced window's wall time over the ticks in it, so host gaps count.

Hand check (yi-6b, 2 layers, vocab 16000): a layer holds 4096*4096*2 +
4096*512*2 + 3*4096*11008 + 2*4096 = 173,023,232 weights; with the head
(65,536,000) and the final norm, 411,586,560, or 823.2 MB in bf16, 1.005 ms
at 819 GB/s.  A KV row is 2 layers * 2 * 4 heads * 128 * 2 B = 4096 B.
"""

import numpy as np


def read(ctx):
    first, end = ctx.stats["traced"] or (0, 0)
    ticks = end - first
    if ticks <= 0:
        return None
    c = ctx.cell.config
    L, D, F = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    H, Hkv, V = c["num_attention_heads"], c["num_key_value_heads"], c["vocab_size"]
    hd = D // H
    per_layer = D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * F + 2 * D
    weights = L * per_layer + D * V + D
    kv = np.asarray(ctx.stats["tick_live_kv"][first:end], np.float64)
    active = np.asarray(ctx.stats["tick_active"][first:end], np.float64)
    kv_bytes = kv * L * 2 * Hkv * hd * 2
    bytes_ = 2 * weights + kv_bytes
    flops = 2 * weights * active + 4 * kv * H * hd * L
    least = np.maximum(flops / ctx.peaks["bf16_flops_per_s"],
                       bytes_ / ctx.peaks["hbm_bytes_per_s"]).sum()
    return 100.0 * least / (ctx.trace.window_ns * 1e-9)
