"""Model FLOPs of the served prefill tokens over the fused launch's device time times the bf16 peak."""

from benchlib import readers


def read(run):
    return readers.fused_step_mfu_pct(run)
