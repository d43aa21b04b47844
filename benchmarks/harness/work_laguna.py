"""What the Laguna decoder needs: operations and bytes computed from
shapes, layer kind by layer kind.  The benchmark's own arithmetic; ``m`` is
a configuration's ``model`` group (the published lists are read up to
``num_hidden_layers``).  Recomputed operations (activation recomputation,
the chunked loss head's second pass) are never counted.  The experts held
here are counted by expectation (a token's ``num_experts_per_tok`` choices
fall on the ``held`` of ``num_experts`` experts ``k * held / E`` times) or,
where a run's counters are at hand, by the assignments they read."""

from __future__ import annotations

SLIDING = "sliding_attention"


def n_held(m: dict) -> int:
    lo, hi = m.get("experts_held") or (0, m["num_experts"])
    return hi - lo


def layers(m: dict) -> range:
    return range(m["num_hidden_layers"])


def attn_matmul_params(m: dict, i: int) -> int:
    """wq and wo of the layer's own head count, wk, wv, the per-head gate."""
    h, hd = m["hidden_size"], m["head_dim"]
    q = m["num_attention_heads_per_layer"][i] * hd
    kv = m["num_key_value_heads"] * hd
    gate = h * m["num_attention_heads_per_layer"][i] if m.get("gating") else 0
    return 2 * h * q + 2 * h * kv + gate


def held_assignments_per_token(m: dict) -> float:
    return m["num_experts_per_tok"] * n_held(m) / m["num_experts"]


def mlp_active_params(m: dict, i: int, held_per_token=None) -> float:
    """Matmul parameters a token passes through in layer ``i``'s MLP;
    ``held_per_token`` is the held experts a token was counted to reach in
    an expert layer (by expectation where None)."""
    h = m["hidden_size"]
    if m["mlp_layer_types"][i] == "dense":
        return 3 * h * m["intermediate_size"]
    if held_per_token is None:
        held_per_token = held_assignments_per_token(m)
    return (h * m["num_experts"]
            + 3 * h * m["shared_expert_intermediate_size"]
            + held_per_token * 3 * h * m["moe_intermediate_size"])


def mlp_stored_params(m: dict, i: int) -> int:
    h = m["hidden_size"]
    if m["mlp_layer_types"][i] == "dense":
        return 3 * h * m["intermediate_size"]
    return (h * m["num_experts"]
            + 3 * h * m["shared_expert_intermediate_size"]
            + n_held(m) * 3 * h * m["moe_intermediate_size"])


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["hidden_size"]


def total_params(m: dict) -> int:
    """Every stored parameter: layers with their two norms, the embedding,
    the untied head, the final norm."""
    h = m["hidden_size"]
    return (sum(attn_matmul_params(m, i) + mlp_stored_params(m, i) + 2 * h
                for i in layers(m)) + 2 * head_params(m) + h)


def active_matmul_params(m: dict, held_per_token=None) -> float:
    return (sum(attn_matmul_params(m, i)
                + mlp_active_params(m, i, held_per_token)
                for i in layers(m)) + head_params(m))


def causal_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2.0


def window_pairs(seq: int, window: int) -> float:
    """sum over positions i of min(i + 1, window)."""
    w = min(window, seq)
    return w * (w + 1) / 2.0 + (seq - w) * float(w)


def layer_pairs(m: dict, i: int, seq: int) -> float:
    if m["layer_types"][i] == SLIDING:
        return window_pairs(seq, m["sliding_window"])
    return causal_pairs(seq)


def attn_flops(m: dict, i: int, pairs: float) -> float:
    """Forward attention operations of layer ``i`` for ``pairs`` (query,
    key) pairs: QK^T and PV, each 2 * head_dim a pair a head."""
    return 4.0 * m["head_dim"] * m["num_attention_heads_per_layer"][i] * pairs


def train_flops_per_token(m: dict, seq: int, held_per_token=None) -> float:
    """Forward + backward = 3 x forward: 6 a matmul parameter the token
    passes through, and each layer's in-mask attention pairs once."""
    attn = sum(attn_flops(m, i, layer_pairs(m, i, seq)) for i in layers(m))
    return (6.0 * active_matmul_params(m, held_per_token)
            + 3.0 * attn / seq)


def layers_of_kind(m: dict, kind: str) -> list:
    return [i for i in layers(m) if m["layer_types"][i] == kind]


def expert_layers(m: dict) -> list:
    return [i for i in layers(m) if m["mlp_layer_types"][i] == "sparse"]


def flash_train_flops(m: dict, kind: str, batch: int, seq: int) -> float:
    """The flash attention forward + backward of one step in the layers of
    ``kind``: 2 matmuls forward, 4 backward, over the in-mask pairs only."""
    return sum(3.0 * attn_flops(m, i, batch * layer_pairs(m, i, seq))
               for i in layers_of_kind(m, kind))


def flash_train_bytes(m: dict, kind: str, batch: int, seq: int,
                      itemsize: int = 2) -> float:
    """Least bytes of the same: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv."""
    kv = batch * seq * m["num_key_value_heads"] * m["head_dim"] * itemsize
    total = 0.0
    for i in layers_of_kind(m, kind):
        q = (batch * seq * m["num_attention_heads_per_layer"][i]
             * m["head_dim"] * itemsize)
        total += (2 * q + 2 * kv) + (4 * q + 4 * kv)
    return total


def expected_assignments(m: dict, tokens: int) -> float:
    """Assignments to held experts of one step, all expert layers, by
    expectation."""
    return tokens * held_assignments_per_token(m) * len(expert_layers(m))


def moe_experts_train_flops(m: dict, assignments: float) -> float:
    """The three grouped products of the held experts, forward + backward,
    for ``assignments`` rows (summed over the expert layers): 3 matmuls x
    2 x h x width an assignment forward, three times that with the
    backward."""
    return 3 * 6.0 * m["hidden_size"] * m["moe_intermediate_size"] * assignments


def moe_experts_train_bytes(m: dict, assignments: float,
                            itemsize: int = 2) -> float:
    """Least bytes of the same: the rows in and out once each way, and each
    expert layer's held experts' weights once each way (read forward; the
    backward pass reads them and writes their gradient: counted once)."""
    weights = (len(expert_layers(m)) * n_held(m) * 3 * m["hidden_size"]
               * m["moe_intermediate_size"])
    return (4.0 * assignments * m["hidden_size"] + 2.0 * weights) * itemsize


def held_load_off(obs: dict) -> float:
    """How far the held experts' load of a run lies from the deployment's:
    the worst expert layer's |assignments to held experts / (all
    assignments x held / E) - 1| over the run's counters.  0.25 is the
    slack of the grouped products' first chunk."""
    import numpy as np

    m, c = obs["model"], obs["counters"]
    held = np.asarray(c["moe_assignments_held"], np.float64).sum(axis=-1)
    whole = np.asarray(c["moe_assignments_total"], np.float64)
    share = n_held(m) / float(m["num_experts"])
    return float(np.max(np.abs(held / (whole * share) - 1.0)))


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, which bound) for work of ``flops`` and ``nbytes``."""
    t_f = flops / peak["flops_per_s_bf16"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
