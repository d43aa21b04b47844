"""Readers of what the harness timed and counted itself."""

from __future__ import annotations

from ..harness import stats


def _window_steps(obs):
    return [(t0, t1, pages, rows) for t0, t1, pages, rows in obs["steps"]
            if 0.0 <= t1 < obs["seconds"]]


def queue_wait_p95_ms(obs, spec):
    waits = [(tr.running_s - tr.plan.due_s) * 1e3 for tr in obs["in_window"]
             if tr.running_s is not None]
    return stats.percentile(waits, 0.95) if waits else None


def step_ms_p50(obs, spec):
    took = [(t1 - t0) * 1e3 for t0, t1, _, _ in _window_steps(obs)]
    return stats.percentile(took, 0.5) if took else None


def pool_in_use_pct(obs, spec):
    steps = _window_steps(obs)
    if not steps:
        return None
    total = obs["engine_args"]["num_blocks"]
    return 100.0 * sum(p for _, _, p, _ in steps) / (len(steps) * total)


def serve_mfu(obs, spec):
    if not obs["work"]["rows"]:
        return None
    return (100.0 * obs["work"]["flops"]
            / (obs["seconds"] * obs["peak"]["flops_per_s_bf16"]))


def serve_hbm_pct(obs, spec):
    if not obs["work"]["steps"]:
        return None
    return (100.0 * obs["work"]["bytes"]
            / (obs["seconds"] * obs["peak"]["hbm_bytes_per_s"]))


def train_mfu(obs, spec):
    from ..harness import work

    if not obs["tokens"]:
        return None
    per_token = work.train_flops_per_token(obs["model"], obs["seq"])
    return (100.0 * per_token * obs["tokens"]
            / (obs["window_s"] * obs["peak"]["flops_per_s_bf16"]))
