"""Operations and bytes the algorithm needs, from the configuration's
shapes (a dense decoder; the file's keys). A multiply-add counts 2.

Per layer, the matrix weights are

    P_layer = d*h*hd (Wq) + 2*d*kh*hd (Wk, Wv) + h*hd*d (Wo) + 3*d*f (MLP)

and the head is ``d*V`` (tied or not). Attention of one query over ``c``
keys costs ``4*h*hd*c`` (scores and the weighted sum). So

* forward of ``n`` tokens whose queries see ``C`` keys in all:
  ``2*L*P_layer*n + 4*h*hd*L*C``, plus ``2*d*V`` for each token whose
  logits are needed;
* prefill of a prompt of ``p`` tokens: ``C = p*(p+1)/2``, logits for the
  last token only;
* one decode token at a cache of ``c`` entries (itself included):
  ``2*(L*P_layer + d*V) + 4*h*hd*L*c``;
* training, per token of a sequence of ``S``: three times the forward
  (forward, and backward for inputs and weights), logits for every token,
  average ``C`` per token ``(S+1)/2``. Recomputation does not count; the
  embedding gather is not a product and does not count.

Bytes (the decode roofline): the weights once per decode step
(``weight_bytes``: every layer's matrices, norms and biases and the head,
at the served type's width) plus each active slot's cache up to its own
position, ``kv_bytes_per_token * c``.
"""
from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * f


def attn_flops_per_key(cfg: dict, layers: int) -> int:
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * layers


def head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg: dict, layers: int, p: int) -> float:
    return (2.0 * layers * layer_matmul_params(cfg) * p
            + attn_flops_per_key(cfg, layers) * p * (p + 1) / 2
            + head_flops(cfg))


def decode_flops(cfg: dict, layers: int, c: int) -> float:
    return (2.0 * layers * layer_matmul_params(cfg) + head_flops(cfg)
            + attn_flops_per_key(cfg, layers) * c)


def train_flops_per_token(cfg: dict, layers: int, seq: int) -> float:
    return 3.0 * (2.0 * layers * layer_matmul_params(cfg) + head_flops(cfg)
                  + attn_flops_per_key(cfg, layers) * (seq + 1) / 2)


def weight_bytes(cfg: dict, layers: int, width: int) -> int:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per = layer_matmul_params(cfg) + 2 * d
    if cfg["attention_bias"]:
        per += (h + 2 * kh) * hd
    return width * (layers * per + d * v + d)


def kv_bytes_per_token(cfg: dict, layers: int, width: int) -> int:
    return (width * 2 * layers * cfg["num_key_value_heads"]
            * cfg["head_dim"])
