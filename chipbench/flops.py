"""Operations and bytes a dense decoder-only LM step requires, from shapes.

Counts are of the work the algorithm needs, not of what a program happens
to do: a matmul of (m, k) by (k, n) is 2*m*k*n operations; causal
attention counts only the (query, key) pairs the mask keeps; the head is
counted over the real vocabulary, not the padded one; recomputation is
not counted.  ``model`` is a configuration dict of the benchmark
(``configs/*.json``), read by its published key names.
"""

from __future__ import annotations


def shapes(model: dict) -> dict:
    return {
        "L": model["num_hidden_layers"],
        "D": model["hidden_size"],
        "N": model["num_attention_heads"],
        "K": model["num_key_value_heads"],
        "Dh": model["head_dim"],
        "F": model["intermediate_size"],
        "V": model["vocab_size"],
    }


def layer_matmul_params(model: dict) -> int:
    """Weights one layer multiplies by: q, k, v, o and the SwiGLU MLP."""
    s = shapes(model)
    D, N, K, Dh, F = s["D"], s["N"], s["K"], s["Dh"], s["F"]
    return D * N * Dh + 2 * D * K * Dh + N * Dh * D + 3 * D * F


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal mask keeps in a sequence of ``seq``."""
    return seq * (seq + 1) // 2


def forward_flops(model: dict, tokens: int, attn_pairs: int, head_tokens: int) -> int:
    """One forward pass over ``tokens`` tokens, attending ``attn_pairs``
    (query, key) pairs in each layer, with logits for ``head_tokens``."""
    s = shapes(model)
    per_layer = 2 * layer_matmul_params(model) * tokens
    per_layer += 4 * s["N"] * s["Dh"] * attn_pairs      # q.k and p.v
    return s["L"] * per_layer + 2 * s["D"] * s["V"] * head_tokens


def train_step_flops(model: dict, batch: int, seq: int) -> int:
    """Forward and backward (twice the forward) of one training step."""
    fwd = forward_flops(model, batch * seq, batch * causal_pairs(seq), batch * seq)
    return 3 * fwd


def prefill_flops(model: dict, batch: int, prompt: int) -> int:
    """Prefill of ``batch`` prompts, logits for the last position only."""
    return forward_flops(model, batch * prompt, batch * causal_pairs(prompt), batch)


def decode_flops(model: dict, batch: int, held: int) -> int:
    """One decode step of ``batch`` sequences that each hold ``held``
    positions in the cache, the new one included."""
    return forward_flops(model, batch, batch * held, batch)


def decode_bytes(model: dict, batch: int, held: int, weight_bytes: int = 2,
                 cache_bytes: int = 2) -> int:
    """Least HBM traffic of one decode step: every weight once (matmuls,
    norms, the head), the embedding rows of the batch, and the cache rows
    the sequences hold (the new row written, the others read).
    Activations are a few KB and left out."""
    s = shapes(model)
    L, D, K, Dh, V = s["L"], s["D"], s["K"], s["Dh"], s["V"]
    weights = L * (layer_matmul_params(model) + 2 * D) + D * V + D
    embed_rows = batch * D
    cache = 2 * L * batch * held * K * Dh
    return weight_bytes * (weights + embed_rows) + cache_bytes * cache
