"""Parameters and model FLOPs of a decoder-only transformer, from the
sizes in a configuration file (``bench/configs/*.json``, ``model`` group,
Hugging Face key names).

Model FLOPs of one training token are 6 per multiply-accumulate weight of
the forward pass (2 forward, 4 backward) plus the attention scores and
their weighted sum, 12 per layer, query width and key position: the
convention of the PaLM paper's appendix B.  The scores are counted over
the whole ``seq x seq`` square, as the program computes them before the
causal mask.  Recomputation is not counted; the embedding lookup is not a
multiplication.  With tied embeddings the output head is the embedding
matrix, and its multiplication counts once.
"""

from __future__ import annotations


def _dims(m: dict):
    d = m["hidden_size"]
    hd = m.get("head_dim") or d // m["num_attention_heads"]
    return (d, m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd,
            m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"])


def param_counts(m: dict) -> dict[str, int]:
    """Parameters by part: ``embedding``, ``blocks`` (attention, MLP and
    the two norms of each layer), ``final_norm`` and ``lm_head`` (0 when
    tied)."""
    d, hq, hk, ff, v, n = _dims(m)
    per_layer = d * (hq + 2 * hk) + hq * d + 3 * d * ff + 2 * d
    return {"embedding": v * d, "blocks": n * per_layer, "final_norm": d,
            "lm_head": 0 if m.get("tie_word_embeddings") else d * v}


def matmul_params(m: dict) -> int:
    """Weights that take part in a multiplication per token."""
    d, hq, hk, ff, v, n = _dims(m)
    return n * (d * (hq + 2 * hk) + hq * d + 3 * d * ff) + d * v


def train_flops_per_token(m: dict, seq: int) -> float:
    d, hq, hk, ff, v, n = _dims(m)
    return 6.0 * matmul_params(m) + 12.0 * n * hq * seq


def train_flops_per_step(m: dict, batch: int, seq: int) -> float:
    return train_flops_per_token(m, seq) * batch * seq
