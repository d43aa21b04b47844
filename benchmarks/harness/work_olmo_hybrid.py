"""What the Olmo-Hybrid decoder needs: operations and least bytes computed
from shapes, layer kind by layer kind, of a row, of a decode step and of
each of the two recurrence kernels.  The benchmark's own arithmetic; ``m``
is the configuration (the published ``layer_types`` is read up to
``num_hidden_layers``).

The recurrence is counted as the algorithm states it, a token at a time,
whatever form a program computes it in: decay ``alpha M``, read ``k^T M``,
update ``k u^T``, output ``q^T M`` over a head's [d_k, d_v] state = 7 d_k
d_v operations a (token, head); a program's chunked form spends more (the
triangular solve, the products inside a sub-chunk) and is credited none of
it."""

from __future__ import annotations

LINEAR = "linear_attention"
F32, BF16 = 4, 2


def kinds(m: dict) -> tuple:
    return tuple(m["layer_types"][:m["num_hidden_layers"]])


def n_linear(m: dict) -> int:
    return sum(k == LINEAR for k in kinds(m))


def n_full(m: dict) -> int:
    return len(kinds(m)) - n_linear(m)


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def lin_dims(m: dict) -> tuple:
    """(heads, d_k, d_v, conv width, conv channels) of a linear layer."""
    H, dk, dv = (m["linear_num_value_heads"], m["linear_key_head_dim"],
                 m["linear_value_head_dim"])
    return (H, dk, dv, m["linear_conv_kernel_dim"],
            2 * m["linear_num_key_heads"] * dk + H * dv)


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def linear_matmul_params(m: dict) -> int:
    """q, k, v, the output gate, the output projection, a and b."""
    h = m["hidden_size"]
    H, dk, dv, _, C = lin_dims(m)
    return h * C + 2 * h * H * dv + 2 * h * H + mlp_params(m)


def full_matmul_params(m: dict) -> int:
    h, hd = m["hidden_size"], head_dim(m)
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return 2 * h * q + 2 * h * kv + mlp_params(m)


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["hidden_size"]


def total_params(m: dict) -> int:
    """Every stored parameter, as the program's tree holds them."""
    h, hd = m["hidden_size"], head_dim(m)
    H, _, dv, K, C = lin_dims(m)
    lin = linear_matmul_params(m) + K * C + 2 * H + dv + 2 * h
    full = (full_matmul_params(m) + 2 * h
            + (m["num_attention_heads"] + m["num_key_value_heads"]) * hd)
    tables = head_params(m) * (1 if m.get("tie_word_embeddings") else 2)
    return n_linear(m) * lin + n_full(m) * full + tables + h


def kv_bytes_per_token(m: dict) -> int:
    """K and V rows of one position, the full layers only."""
    return 2 * m["num_key_value_heads"] * head_dim(m) * BF16 * n_full(m)


def state_bytes_per_slot(m: dict) -> int:
    """A slot's recurrent state (float32) and conv window, every linear
    layer."""
    H, dk, dv, K, C = lin_dims(m)
    return n_linear(m) * (H * dk * dv * F32 + (K - 1) * C * BF16)


def weight_bytes_per_step(m: dict) -> int:
    """Bytes of weights one forward step has to stream: every layer's
    matrices and the head (embedding rows are a lookup)."""
    return (n_linear(m) * linear_matmul_params(m)
            + n_full(m) * full_matmul_params(m) + head_params(m)) * BF16


def recurrence_flops_per_row(m: dict) -> float:
    """One linear layer, one token: the recurrence over every head and the
    conv."""
    H, dk, dv, K, C = lin_dims(m)
    return 7.0 * H * dk * dv + 2.0 * K * C


def serve_row_flops(m: dict, rows: int, pairs: float, logits: int) -> float:
    """Forward operations of serving ``rows`` token rows through the layers
    with ``pairs`` attention pairs (a full layer), and ``logits`` rows
    through the head."""
    matmuls = (n_linear(m) * linear_matmul_params(m)
               + n_full(m) * full_matmul_params(m))
    attn = 4.0 * head_dim(m) * m["num_attention_heads"] * n_full(m) * pairs
    return (2.0 * matmuls * rows + attn
            + n_linear(m) * recurrence_flops_per_row(m) * rows
            + 2.0 * head_params(m) * logits)


def causal_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2.0


def window_work(tracks, steps, m: dict, seconds: float) -> dict:
    """Operations and least bytes of what the window served, from what the
    harness saw, as ``runners/serve.window_work`` counts the dense model's:
    a prompt's rows are credited when its first token comes, a decode row
    when its token comes.  Bytes: the weights once a step, K/V of the full
    layers read for a decode row and written once a token, and a slot's
    recurrent state read and written once a generated row and once a
    prompt."""
    rows = pairs = logits = 0
    kv_read = kv_written = state_passes = 0
    for tr in tracks:
        p = tr.plan.prompt_ids.size
        for i, t in enumerate(tr.token_s):
            if not 0.0 <= t < seconds:
                continue
            logits += 1
            state_passes += 1
            if i == 0:
                rows += p
                pairs += causal_pairs(p)
                kv_written += p
            else:
                rows += 1
                pairs += p + i
                kv_read += p + i
                kv_written += 1
    n_steps = sum(1 for t0, t1, *_ in steps if 0.0 <= t1 < seconds)
    return {"flops": serve_row_flops(m, rows, pairs, logits),
            "bytes": (n_steps * weight_bytes_per_step(m)
                      + (kv_read + kv_written) * kv_bytes_per_token(m)
                      + 2 * state_passes * state_bytes_per_slot(m)),
            "steps": n_steps, "rows": rows}


# ---- the two recurrence kernels, summed over the linear layers ----

def gdn_work(m: dict, rows_live: float, lanes_live: float) -> tuple:
    """(operations, least bytes) of the recurrence of ``rows_live`` token
    rows spread over ``lanes_live`` (slot, launch) pairs, every linear
    layer: a row's q, k, v in and o out as the kernels take them (float32),
    a live lane's state read and written once."""
    H, dk, dv, _, _ = lin_dims(m)
    flops = 7.0 * H * dk * dv * rows_live
    nbytes = (rows_live * H * (2 * dk + 2 * dv) * F32
              + lanes_live * 2 * H * dk * dv * F32)
    return n_linear(m) * flops, n_linear(m) * nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, which bound) for work of ``flops`` and ``nbytes``."""
    t_f = flops / peak["flops_per_s_bf16"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
