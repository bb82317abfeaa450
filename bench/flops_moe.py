"""Operations and bytes of a Qwen3-MoE expert share, from the
configuration's shapes (the file's keys) and the rows the router sent to
the held experts (the step's ``moe.rows`` counter). A multiply-add
counts 2.

Per layer, the matrix weights every token goes through are

    P_dense = d*h*hd (Wq) + 2*d*kh*hd (Wk, Wv) + h*hd*d (Wo) + d*E (router)

and each row routed to a held expert goes through ``3*d*F`` (gate, up,
down). Forward of ``n`` tokens of a sequence of ``S`` over ``L`` layers
with ``R`` held rows in all: ``2*L*P_dense*n + 4*h*hd*L*n*(S+1)/2 +
2*d*V*n + 6*d*F*R``; training is three times that (forward, and backward
for inputs and weights). Recomputation does not count; the embedding
gather, norms, softmax and routing's sort are not products.

The grouped matmul (``model.moe.ffn``): ``18*d*F*R`` a step. Its bytes,
at the bf16 it computes in: each (layer, microbatch) call reads the held
experts' three weight matrices once and each routed row in (``d``) and
out (``d``); the backward pass moves twice the forward's.
"""
from __future__ import annotations

BF16 = 2


def dense_layer_params(cfg: dict) -> int:
    d, h, kh, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * hd + 2 * d * kh * hd + h * hd * d + d * cfg["router_experts"]


def expert_row_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def train_flops(cfg: dict, layers: int, tokens: int, seq: int,
                held_rows: float) -> float:
    """One training step of ``tokens`` tokens in sequences of ``seq``,
    ``held_rows`` rows routed to held experts over all layers."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    fwd = (2.0 * layers * dense_layer_params(cfg) * tokens
           + 4.0 * h * hd * layers * tokens * (seq + 1) / 2
           + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * tokens
           + 2.0 * expert_row_params(cfg) * held_rows)
    return 3.0 * fwd


def ffn_flops(cfg: dict, held_rows: float) -> float:
    """The grouped matmul's operations in a step, forward and backward."""
    return 3.0 * 2.0 * expert_row_params(cfg) * held_rows


def ffn_bytes(cfg: dict, calls: int, held_rows: float) -> float:
    """The grouped matmul's bytes in a step of ``calls`` (layer,
    microbatch) calls and ``held_rows`` routed rows in all."""
    weights = cfg["num_experts"] * expert_row_params(cfg) * BF16
    rows = 2 * cfg["hidden_size"] * BF16 * held_rows
    return 3.0 * (calls * weights + rows)
