"""``bench/flops.py`` against counts made by hand at the published shapes."""
import json
from pathlib import Path

import bench_tiny_root  # noqa: F401
from bench import flops

CFG = Path(__file__).resolve().parents[1] / "configs"
QWEN = json.loads((CFG / "qwen2_5_3b.json").read_text())
STABLELM = json.loads((CFG / "stablelm_1_6b.json").read_text())


def test_layer_weights():
    # qwen2.5-3b: Wq 2048x2048, Wk and Wv 2048x256, Wo 2048x2048, three
    # MLP matrices 2048x11008
    assert flops.layer_matmul_params(QWEN) == (
        4_194_304 + 2 * 524_288 + 4_194_304 + 3 * 22_544_384) == 77_070_336
    # stablelm-2-1.6b: four 2048x2048 attention matrices, MLP 2048x5632
    assert flops.layer_matmul_params(STABLELM) == (
        4 * 4_194_304 + 3 * 11_534_336) == 51_380_224


def test_cache_bytes_per_token():
    # 2 (K and V) x layers x kv heads x head dim x 2 bytes
    assert flops.kv_bytes_per_token(STABLELM, 24, 2) == 196_608
    assert flops.kv_bytes_per_token(QWEN, 36, 2) == 36_864


def test_weight_bytes_qwen_bf16():
    # per layer: matrices + two norms (4,096) + q/k/v biases (2,560);
    # then the tied 151,936 x 2,048 table and the final norm
    params = 36 * (77_070_336 + 4_096 + 2_560) + 311_164_928 + 2_048
    assert params == 3_085_938_688
    assert flops.weight_bytes(QWEN, 36, 2) == 2 * params


def test_prefill_and_decode_flops_qwen():
    # a 256-token prompt: 2 x 36 x 77,070,336 x 256 for the matrices,
    # 4 x 16 x 128 x 36 x (256 x 257 / 2) for attention, one head row
    assert flops.prefill_flops(QWEN, 36, 256) == (
        1_420_560_433_152 + 9_701_425_152 + 622_329_856)
    # one token at a cache of 300 entries
    assert flops.decode_flops(QWEN, 36, 300) == (
        5_549_064_192 + 622_329_856 + 294_912 * 300)


def test_train_flops_per_token_qwen_4_layers():
    # 3 x (2 x 4 x 77,070,336 + 2 x 2048 x 151,936 + 32,768 x 256.5)
    assert flops.train_flops_per_token(QWEN, 4, 512) == 3 * (
        616_562_688 + 622_329_856 + 8_404_992)
