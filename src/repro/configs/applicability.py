"""Shape-cell applicability (DESIGN.md §5).

``long_500k`` requires sub-quadratic attention: run for SSM/hybrid archs and
for sliding-window attention (rolling-buffer cache); skip for pure
full-attention archs (their global layers would need the full 500k KV under
quadratic semantics).  Dense archs *can* run long_500k in SSA-linear mode —
that is exercised separately as a beyond-paper experiment, not a baseline
cell.
"""
from __future__ import annotations

_LONG_OK = {
    "xlstm_125m": "O(1)-state recurrent decode (mLSTM/sLSTM)",
    "zamba2_1_2b": "Mamba2 state + shared-attn over seq-sharded cache",
    "mixtral_8x7b": "SWA rolling-buffer KV cache (window 4096)",
}

_LONG_SKIP = {
    "gemma2_9b": "global layers are full attention (local/global alternation)",
    "codeqwen15_7b": "pure full attention",
    "phi4_mini_3_8b": "pure full attention",
    "yi_34b": "pure full attention",
    "qwen2_vl_2b": "pure full attention",
    "deepseek_moe_16b": "pure full attention",
    "whisper_small": "enc-dec, decoder max target 448; no 500k decode semantics",
    "mellum2_12b_a2_5b": "every fourth layer is full attention (window 1024 elsewhere)",
}


def cell_status(arch: str, shape: str) -> tuple[str, str]:
    if shape == "long_500k":
        if arch in _LONG_OK:
            return "run", _LONG_OK[arch]
        return "skip", _LONG_SKIP[arch]
    return "run", ""
