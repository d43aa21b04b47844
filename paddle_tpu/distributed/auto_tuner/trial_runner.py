"""Measured trial runner (reference: auto_tuner/tuner.py:21 — the reference
launches each surviving candidate as a REAL distributed trial job and records
its metric; this is the TPU/mesh analog).

``make_llama_trial_runner`` returns a ``run_trial(candidate) -> metric``
callable for :class:`..auto_tuner.tuner.AutoTuner`: it builds the Llama train
step on the candidate's mesh factorization (real devices when present, the
8-virtual-CPU mesh in tests), jits one step for compile, times the next N
with a host-fetch barrier, and returns mean SECONDS PER SAMPLE (the batch
weak-scales with the factorization, so per-sample time — throughput rank —
is the comparable unit; see make_llama_trial_runner).  A candidate that
fails to build or OOMs raises — the tuner records the error and moves on,
exactly the reference's failed-trial semantics.
"""

from __future__ import annotations

import os
import time

__all__ = ["make_llama_trial_runner"]


def make_llama_trial_runner(model_cfg=None, seq: int = 64,
                            micro_rows: int = 1, warmup: int = 1,
                            steps: int = 3, devices=None):
    """Build a measuring ``run_trial`` over a (default tiny) LlamaConfig.

    Candidate mapping: the tuner's ``sharding_degree`` divides ``dp_degree``
    (the reference's hybrid convention, prune.py:25), so the mesh gets
    dp = dp_degree // sharding_degree and sharding = sharding_degree axes;
    ``micro_batch_size`` scales rows per (dp x sharding) shard per
    microbatch; ``use_recompute`` selects the remat policy the model reads
    at trace time (PADDLE_TPU_REMAT).

    Metric: the batch weak-scales with the factorization (dp x sharding x
    microbatches), so the returned metric is SECONDS PER SAMPLE, not raw
    step time — candidates are ranked by throughput, and an mp=2 candidate
    (half the tokens/step of dp=2) can't win merely by doing less work per
    step.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ...models import llama

    cfg = model_cfg or llama.LlamaConfig.tiny(
        vocab=256, hidden=64, layers=4, heads=4, kv_heads=2, inter=128)

    def run_trial(cand) -> float:
        dp_total = cand["dp_degree"]
        mp = cand["mp_degree"]
        pp = cand["pp_degree"]
        shard = cand.get("sharding_degree", 1)
        assert dp_total % shard == 0, (dp_total, shard)
        dp = dp_total // shard
        n = dp_total * mp * pp
        devs = list(devices) if devices is not None else jax.devices()
        if len(devs) < n:
            raise RuntimeError(f"candidate needs {n} devices, have {len(devs)}")
        mesh = llama.make_mesh(dp=dp, mp=mp, sharding=shard, pp=pp,
                               devices=devs[:n])

        mbs = int(cand.get("micro_batch_size", 1))
        M = pp if pp > 1 else 1                    # microbatches
        # weak-scaled batch, normalized to seconds/sample below so an mp=2
        # candidate (half the tokens/step of dp=2) can't win on raw step
        # time while losing on throughput
        batch = max(1, mbs * micro_rows) * dp * shard * M
        prev = os.environ.get("PADDLE_TPU_REMAT")
        os.environ["PADDLE_TPU_REMAT"] = (
            "full" if cand.get("use_recompute") else "none")
        try:
            step_fn, opt_init, pshard, dshard = llama.build_train_step(
                cfg, mesh, num_microbatches=M if pp > 1 else None)
            params = jax.device_put(llama.init_params(cfg, jax.random.key(0)),
                                    pshard)
            opt_state = opt_init(params)
            rs = np.random.RandomState(0)
            ids = jax.device_put(
                jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq))), dshard)
            labels = jax.device_put(
                jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq))), dshard)
            for _ in range(max(1, warmup)):  # >=1: compile must stay untimed
                loss, params, opt_state = step_fn(params, opt_state, ids, labels)
            float(loss)  # host fetch = barrier (compile stays untimed)
            n_steps = max(1, steps)
            t0 = time.perf_counter()
            for _ in range(n_steps):
                loss, params, opt_state = step_fn(params, opt_state, ids, labels)
            float(loss)
            return (time.perf_counter() - t0) / n_steps / batch
        finally:
            if prev is None:
                os.environ.pop("PADDLE_TPU_REMAT", None)
            else:
                os.environ["PADDLE_TPU_REMAT"] = prev

    return run_trial
