"""Tiny cells for the CPU: the runners' whole control flow at a size a
test can hold (widths of ``LlamaConfig.tiny()``, float32 so that the
program and the reference differ by rounding of float32 alone)."""

import time

from benchmarks.harness.cell import Cell

MODEL = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
         "rope_theta": 10000.0, "sliding_window": None,
         "tie_word_embeddings": False, "hidden_act": "silu",
         "torch_dtype": "float32"}
CONFIG = {"name": "tiny", "reference": "benchmarks.reference.llama_dense",
          "model": MODEL}
PEAK = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
ENGINE = {"paged": True, "enable_chunked_prefill": True, "max_batch": 4,
          "max_seq": 128, "block_size": 16, "num_blocks": 24,
          "prefill_chunk": 16}


def serve_mix(process="poisson", rate=40.0, limit=1e-3):
    return {"name": "tiny-" + process, "kind": "serve",
            "arrivals": {"process": process, "requests_per_second": rate},
            "prompt_tokens": {"dist": "lognormal", "median": 24,
                              "sigma": 0.5, "min": 8, "max": 60},
            "max_new_tokens": {"dist": "lognormal", "median": 6,
                               "sigma": 0.4, "min": 3, "max": 12},
            "schedule_seed": 0, "preroll_s": 0.2, "drain_s": 30.0,
            "warmup": {"requests": 2, "prompt_tokens": 20,
                       "max_new_tokens": 3},
            "engine": ENGINE,
            "check": {"requests": 3, "logit_gap_limit": limit},
            "trace_s": 0.3}


def train_job(limits=None):
    return {"name": "tiny-train", "kind": "train", "batch": 4, "seq": 32,
            "mesh": {"dp": 1, "mp": 1},
            "optimizer": {"lr": 3e-4, "weight_decay": 0.1, "beta1": 0.9,
                          "beta2": 0.95, "grad_clip": 1.0},
            "optimizer_assumed": {"eps": 1e-8},
            "env": {"PADDLE_TPU_XENT_CHUNK": "16"},
            "check": {"steps": 3, "limits": limits},
            "trace_s": 0.2}


def cell(mix, seed=7, seconds=1.0, trace=False, tmp="/tmp"):
    return Cell(name="tiny." + mix["name"], config=CONFIG, mix=mix, chips=1,
                seed=seed, seconds=seconds, trace=trace, peak=PEAK,
                t0=time.perf_counter(), trace_dir=str(tmp))
