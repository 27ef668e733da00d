"""Required training operations per token of granite-3.0-1b-a400m (GQA
attention, top-k routed SwiGLU experts, tied head), from the sizes in the
configuration file.

Forward, per layer: q, k, v, o projections; causal attention (QKᵀ and PV
over an average context of (S + 1) / 2); the router; k experts of three
d × f matrices each.  Then the head, d × vocab.  Each matmul counts 2
operations per multiply-add; the backward pass counts twice the forward
(gradients of activations and of weights), so training is 3× forward.
Norms, RoPE, softmax, the embedding lookup, MoE padding and rematerialised
work are not counted.
"""
from __future__ import annotations


def forward_per_token(c: dict, seq: int) -> float:
    d = c["hidden_size"]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    e, k, f = (c["num_local_experts"], c["num_experts_per_tok"],
               c["intermediate_size"])
    proj = 2 * d * (h * hd + 2 * hkv * hd) + 2 * h * hd * d
    attn = 2 * 2 * h * hd * (seq + 1) / 2
    router = 2 * d * e
    experts = k * 3 * 2 * d * f
    layer = proj + attn + router + experts
    head = 2 * d * c["vocab_size"]
    return c["num_hidden_layers"] * layer + head


def train_per_token(c: dict, seq: int) -> float:
    return 3.0 * forward_per_token(c, seq)
