"""Exact per-architecture configs (one module per assigned architecture).

Import side-effect free; each module exports ``CONFIG`` plus a
``smoke_config()`` returning a reduced same-family config for CPU tests.
"""

from repro.configs import (
    gemma2_2b,
    grok_1_314b,
    mistral_nemo_12b,
    mixtral_8x7b,
    moonlight_16b_a3b,
    pixtral_12b,
    qwen1_5_0_5b,
    whisper_base,
    xlstm_350m,
    yi_6b,
    zamba2_2_7b,
)

ALL_CONFIGS = {
    m.CONFIG.name: m.CONFIG
    for m in [
        whisper_base,
        xlstm_350m,
        gemma2_2b,
        mistral_nemo_12b,
        yi_6b,
        qwen1_5_0_5b,
        pixtral_12b,
        grok_1_314b,
        mixtral_8x7b,
        zamba2_2_7b,
        moonlight_16b_a3b,
    ]
}

SMOKE_CONFIGS = {
    m.CONFIG.name: m.smoke_config()
    for m in [
        whisper_base,
        xlstm_350m,
        gemma2_2b,
        mistral_nemo_12b,
        yi_6b,
        qwen1_5_0_5b,
        pixtral_12b,
        grok_1_314b,
        mixtral_8x7b,
        zamba2_2_7b,
        moonlight_16b_a3b,
    ]
}
