"""Pallas TPU kernel library.

The irreducible native-kernel set identified in SURVEY.md §2 ("Native-component
summary"): flash attention, ragged paged-attention decode (docs/
paged_attention.md), fused rms_norm, rotary embedding, swiglu, and MoE
dispatch.  Everything else in the reference's 525k-LoC kernel library lowers
through XLA.  Each kernel here:

- runs compiled on TPU, and in interpreter mode on CPU (so the OpTest-style
  suite can check parity against numpy/XLA oracles without hardware);
- has a jax.custom_vjp so it composes with both the eager tape and jit/grad.
"""

from __future__ import annotations

import contextlib

import jax


def on_tpu() -> bool:
    # a backend that fails to initialize raises here: trouble with the
    # device must never turn the kernels into their interpreter quietly
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas interpret=True off-TPU so kernels stay testable on CPU CI."""
    return not on_tpu()


# GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map"): a jitted
# program for a mesh of more than one device has to run each kernel per
# shard.  The program that owns the mesh (models/llama.build_train_step)
# traces under ``spmd_kernels`` and the kernel entries (rms_norm,
# flash_attention_bshd) wrap their launch with ``per_shard``.  Serving's TP
# path is already one explicit shard_map region and never sets this.
_SPMD = None    # (mesh, batch_axes, head_axis) while such a program traces


@contextlib.contextmanager
def spmd_kernels(mesh, batch_axes, head_axis):
    """While tracing inside: activations are split over ``batch_axes`` on
    their leading (batch) dim and over ``head_axis`` on their heads dim."""
    global _SPMD
    prev = _SPMD
    _SPMD = (mesh, batch_axes, head_axis) if mesh.size > 1 else None
    try:
        yield
    finally:
        _SPMD = prev


def per_shard(fn, specs_of):
    """``fn`` as it must be launched: itself on one device, or inside a
    ``shard_map`` over the ``spmd_kernels`` mesh with the
    ``(in_specs, out_specs)`` that ``specs_of(batch_axes, head_axis)``
    gives."""
    if _SPMD is None:
        return fn
    mesh, batch_axes, head_axis = _SPMD
    in_specs, out_specs = specs_of(batch_axes, head_axis)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# the full opt-out vocabulary: every kernel_disabled() dispatch site in the
# package plus 'all'.  kernel_disabled() validates against it at parse time
# so a typo ('paged_attn') warns with a did-you-mean instead of silently
# keeping the kernel it was meant to disable (utils/envflags.py).  The set
# is cross-checked BOTH ways by the KNOWN_KERNELS drift lint
# (analysis/kernel_contracts.registry_drift_findings, gated by
# tools/lint_gate.py --strict-allowlist): a token with no dispatch site is
# a dead kill switch, a dispatch site with no token loses the typo guard.
# 'rope' and 'swiglu' were retired by that lint: both ops are pure jnp
# (XLA fuses them; SURVEY.md §7) with no Pallas kernel to route around, so
# their opt-outs disabled nothing — setting them now warns instead.
# 'fused_layer_mlp' and 'fused_quant_append' are the decode-megastep
# stage-2 per-path switches (docs/paged_attention.md "Megastep stage 2"):
# the former restores the stage-1 per-layer program (rms_norm launch +
# XLA MLP), the latter sends int8/int4 KV pools back to the
# requant-scatter append ('fused_decode_step' disables both fused decode
# members at once).
KNOWN_KERNELS = frozenset({"all", "flash_attention", "rms_norm",
                           "paged_attention", "flash_decode",
                           "fused_decode_step", "fused_layer_mlp",
                           "fused_quant_append"})


def kernel_disabled(name: str) -> bool:
    """Operational escape hatch: route around a Pallas kernel at runtime.

    ``PADDLE_TPU_DISABLE_PALLAS="flash_attention,rms_norm"`` (or ``"all"``)
    switches the named kernels to their XLA-composed fallbacks — an
    explicit operator opt-out; nothing in the repo sets it on its own.
    Values outside :data:`KNOWN_KERNELS` warn once
    (typo guard) but are still honored as opt-outs.  The queried ``name``
    is always accepted as known — a future kernel that guards itself with
    ``kernel_disabled("new_kernel")`` must not make its own legitimate
    opt-out warn as a typo just because the frozenset lagged."""
    from ...utils.envflags import env_token_set

    names = env_token_set("PADDLE_TPU_DISABLE_PALLAS", KNOWN_KERNELS | {name})
    return bool(names) and ("all" in names or name in names)
