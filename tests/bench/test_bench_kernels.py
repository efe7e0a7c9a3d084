"""FLOP and byte counts of each kernel and of the model step, against
counts made by hand."""
import json
from types import SimpleNamespace

import pytest

from bench import manifest


def test_flash_attention_counts():
    k = manifest.load_module("kernels", "flash_attention")
    # 1 row, 4 positions, 2 query heads over 1 kv head of size 8:
    # query i meets i + 1 keys -> 1+2+3+4 = 10 pairs, 2 matmuls of
    # 2 FLOPs per multiply-add over 8 dims, for each of 2 heads
    f, b = k.cost(B=1, S=4, H=2, K=1, hd=8)
    assert f == 10 * 2 * 2 * 8 * 2
    # q and out: 4 x 2 x 8 each; k and v: 4 x 1 x 8 each; bf16
    assert b == 2 * (4 * 2 * 8 * 2 + 4 * 1 * 8 * 2)


def test_decode_attention_counts():
    k = manifest.load_module("kernels", "decode_attention")
    f, b = k.cost(B=2, n_keys=5, H=4, K=2, hd=8)
    assert f == 2 * 4 * 5 * 8 * 2 * 2
    # per row: k and v of 5 keys x 2 heads x 8, q and out of 4 x 8, in
    # bf16; and the 5 int32 position ids
    assert b == 2 * (2 * (2 * 5 * 2 * 8 + 2 * 4 * 8) + 4 * 5)


def test_kernel_calls_follow_the_run_records():
    k = manifest.load_module("kernels", "decode_attention")
    run = SimpleNamespace(dims={"L": 3, "H": 4, "K": 2, "hd": 8},
                          decode_steps=[(1, 10), (8, 11)], prefill_batches=[8],
                          prompt_tokens=16)
    calls = k.calls(run)
    assert len(calls) == 2 * 3
    assert calls[0] == k.cost(1, 11, 4, 2, 8)
    f = manifest.load_module("kernels", "flash_attention")
    assert f.calls(run) == [f.cost(8, 16, 4, 2, 8)] * 3


def _tiny_config():
    cfg = json.loads((manifest.BENCH_DIR / "configs" / "qwen2-0.5b.json").read_text())
    cfg.update(hidden_size=8, intermediate_size=16, num_attention_heads=2,
               num_key_value_heads=1, num_hidden_layers=3, vocab_size=10,
               tie_word_embeddings=True)
    cfg["model"]["head_dim"] = 4
    return cfg


def test_model_flops_by_hand():
    fam = manifest.load_module("families", "dense_gqa")
    cfg = _tiny_config()
    # per layer: q 8x2x4, k and v 8x1x4 each, o 2x4x8, mlp 3 x 8x16
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert fam.matmul_params(cfg) == (3 * per_layer, 80)
    # prefill of 2 rows x 5 positions: matmuls at every position, causal
    # attention over 15 pairs, the head at the last position only
    want = 2 * (2 * 5 * 3 * per_layer + 4 * 2 * 4 * 15 * 3 + 2 * 80)
    assert fam.prefill_flops(cfg, 2, 5) == pytest.approx(want)
    # one decode step at position 5 attends to 6 keys
    want = 2 * (2 * 3 * per_layer + 4 * 2 * 4 * 6 * 3 + 2 * 80)
    assert fam.decode_flops(cfg, 2, 5) == pytest.approx(want)
