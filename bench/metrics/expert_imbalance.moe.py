"""MoE model: the most prefill (token, choice) pairs any held expert took over the held experts' mean, per MoE layer, averaged over the window's rounds."""

from benchlib import moe_readers


def read(run):
    return moe_readers.expert_imbalance(run)
