"""Work computed from a configuration's shapes, never from the program.

Every function takes the configuration file's dict (``chipbench/
configs/<name>.json``) and counts what the algorithm needs, whatever
implements it: model FLOPs per decoded token, KV page bytes, and the
bytes a page crossing (decrypt + verify, or encrypt + MAC) must move.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
MAC_BYTES = 16          # one page MAC (the pool keeps one per page)


def kv_bytes_per_token(c: dict) -> int:
    """K and V of one token over every layer."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * DTYPE_BYTES[c["dtype"]])


def page_bytes(c: dict, page_tokens: int) -> int:
    """Bytes of one KV page (K and V, every layer) as plaintext."""
    return kv_bytes_per_token(c) * page_tokens


def crossing_bytes(c: dict, page_tokens: int, pages: int) -> int:
    """HBM bytes a crypt + MAC pass over ``pages`` pages must move:
    ciphertext in, plaintext out (or the reverse), and the pages' MACs
    (one per page for K and one for V)."""
    return pages * (2 * page_bytes(c, page_tokens) + 2 * MAC_BYTES)


def matmul_params(c: dict) -> int:
    """Weights a decoded token multiplies through, LM head included."""
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    ff = c["intermediate_size"]
    attn = d * h * hd * 2 + d * kv * hd * 2
    mlp = d * ff * (3 if c["hidden_act"] == "silu-gated" else 2)
    return c["num_hidden_layers"] * (attn + mlp) + d * c["vocab_size"]


def decode_flops(c: dict, context: int) -> int:
    """Model FLOPs of one decoded token that attends over ``context``
    earlier tokens: 2 per weight, plus QK^T and PV."""
    attn = (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * context)
    return 2 * matmul_params(c) + attn
