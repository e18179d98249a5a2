"""MoE model: latent-attention and expert prefill FLOPs of the traced fused launches, routed work from the program's counters, over the launches' device time times the bf16 peak."""

from benchlib import moe_readers


def read(run):
    return moe_readers.prefill_mfu_pct(run)
