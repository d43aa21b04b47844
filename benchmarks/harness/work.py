"""What the algorithm needs: operations and bytes computed from shapes.

The benchmark's own copy of the arithmetic (the program's is
``paddle_tpu.models.llama.flops_per_token`` / ``attn_flops_per_token``): a
later PR may change the program's and may not change this yardstick.
Recomputed operations (activation recomputation, the chunked loss head's
second pass) are never counted.  ``m`` is a configuration's ``model``
group."""

from __future__ import annotations


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def layer_matmul_params(m: dict) -> int:
    h, i = m["hidden_size"], m["intermediate_size"]
    q = m["num_attention_heads"] * head_dim(m)
    kv = m["num_key_value_heads"] * head_dim(m)
    return h * q + 2 * h * kv + q * h + 3 * h * i


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["hidden_size"]


def total_params(m: dict) -> int:
    """Every stored parameter: layers with their two norms, the embedding,
    the untied head, the final norm."""
    h = m["hidden_size"]
    tables = head_params(m) * (1 if m.get("tie_word_embeddings") else 2)
    return (m["num_hidden_layers"] * (layer_matmul_params(m) + 2 * h)
            + tables + h)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    return (2 * m["num_key_value_heads"] * head_dim(m) * itemsize
            * m["num_hidden_layers"])


def weight_bytes_per_step(m: dict, itemsize: int = 2) -> int:
    """Bytes of weights one forward step has to stream: every layer's
    matrices and the head (embedding rows are a lookup)."""
    return (m["num_hidden_layers"] * layer_matmul_params(m)
            + head_params(m)) * itemsize


def attn_flops(m: dict, pairs: float) -> float:
    """Forward attention operations for ``pairs`` (query, key) pairs summed
    over the batch: QK^T and PV, each 2 * head_dim per pair per head."""
    return (4.0 * head_dim(m) * m["num_attention_heads"]
            * m["num_hidden_layers"] * pairs)


def causal_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2.0


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward = 3 x forward: 6 per matmul parameter, and the
    causal half of attention counted once."""
    dense = m["num_hidden_layers"] * layer_matmul_params(m) + head_params(m)
    return 6.0 * dense + 3.0 * attn_flops(m, causal_pairs(seq)) / seq


def serve_row_flops(m: dict, rows: int, pairs: float, logits: int) -> float:
    """Forward operations of serving ``rows`` token rows through the layers
    with ``pairs`` attention pairs, and ``logits`` rows through the head."""
    return (2.0 * m["num_hidden_layers"] * layer_matmul_params(m) * rows
            + attn_flops(m, pairs) + 2.0 * head_params(m) * logits)


def flash_attn_train_flops(m: dict, batch: int, seq: int) -> float:
    """Flash attention forward + backward of one step: 2 matmuls forward, 4
    backward (the recomputed scores are not counted), causal half."""
    return 3.0 * attn_flops(m, batch * causal_pairs(seq))


def flash_attn_train_bytes(m: dict, batch: int, seq: int,
                           itemsize: int = 2) -> float:
    """Least bytes of the same: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv."""
    q = batch * seq * m["num_attention_heads"] * head_dim(m) * itemsize
    kv = batch * seq * m["num_key_value_heads"] * head_dim(m) * itemsize
    per_layer = (2 * q + 2 * kv) + (4 * q + 4 * kv)
    return float(per_layer * m["num_hidden_layers"])


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, which bound) for work of ``flops`` and ``nbytes``."""
    t_f = flops / peak["flops_per_s_bf16"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
