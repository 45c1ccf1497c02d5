"""Operation and byte counts at the published shapes, and the peak table."""
import json

import pytest

from bench import counts, harness

from conftest import ROOT


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


LM = "codeqwen15-7b-ssa-packed-16l"


def test_lm_weights_per_token():
    cfg = config(LM)
    # per layer: q,k,v (32 + 2 * 4 heads of 128) and o, then a 3-matrix SwiGLU
    assert counts.matmul_params(cfg) == 16 * (4096 * 40 * 128 + 4096 * 4096
                                              + 3 * 4096 * 13440)
    assert counts.matmul_params(cfg) == 3_246_391_296
    assert counts.head_params(cfg) == 4096 * 92416


def test_one_decode_call_at_published_shapes():
    """One decode token at position 699 over a 700-token packed cache:
    one query row, 700 visible keys of 4 KV heads, one bit per spike; no
    128-row query padding, no page tiles, no heads repeated for the
    query groups."""
    cfg = config(LM)
    ops, nbytes = counts.ssa_call(cfg, [699], 700)
    assert ops == 16 * 32 * 4 * 4 * 128 * 700 == 734_003_200
    per_layer = 2 * (700 * 4 * 4 * 128 // 8) + 2 * (1 * 4 * 32 * 128 // 8)
    assert nbytes == 16 * per_layer == 5_799_936
    assert counts.token_ops(cfg, 700, logits=True) == (
        2 * 3_246_391_296 + 734_003_200 + 2 * 4096 * 92416)


def test_a_chunk_counts_causal_keys_only():
    cfg = config(LM)
    ops, _ = counts.ssa_call(cfg, list(range(16, 32)), 32)
    assert ops == counts.ssa_ops(cfg, 1) * sum(range(17, 33))


def test_peak_table_holds_v5e():
    p = harness.load_peaks("TPU v5 lite")
    assert p == {"bf16_flops": 197e12, "int8_ops": 393e12,
                 "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    src = json.loads((ROOT / "bench" / "peaks.json").read_text())["source"]
    assert "TPU v5e" in src


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.load_peaks("TPU v9 imaginary")
