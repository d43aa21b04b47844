"""Collective micro-benchmarks (reference: fleet.collective_perf,
python/paddle/distributed/fleet/fleet.py:632, impl :572 — allreduce/
broadcast/reduce/allgather/reduce_scatter bandwidth checks with
expected-time warnings).

TPU-native: each collective runs as a jitted ``shard_map`` over one axis of
the hybrid mesh (XLA collectives over ICI), timed with host-fetch barriers
(a fetch, like ``block_until_ready``, returns only when the device is
done).  Doubles as an ICI health probe: a healthy chip has a stable s/iter
signature per size, so a sudden regression is quantitative evidence of link
trouble.
"""

from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger("paddle_tpu.fleet")

_COMM_TYPES = ("allreduce", "reduce", "broadcast", "allgather",
               "reduce_scatter", "p2p")


def _axis_for(comm_type: str, shape: dict) -> str | None:
    """Reference group choice (fleet.py:584-599): data axis (dp, else
    sharding) for allreduce/reduce/broadcast; mp for allgather/
    reduce_scatter.  Falls back to ANY nontrivial axis, else None."""
    prefer = (("data", "dp", "sharding") if comm_type in
              ("allreduce", "reduce", "broadcast")
              else ("pipe", "pp", "model", "mp") if comm_type == "p2p"
              else ("model", "mp"))
    for a in prefer:
        if shape.get(a, 1) > 1:
            return a
    for a, n in shape.items():
        if n > 1:
            return a
    return None


def _build_op(comm_type: str, mesh: Mesh, axis: str | None):
    spec = P(axis) if axis else P()

    def body(x):
        if axis is None:
            return x + 0.0  # single-participant: measures dispatch+fetch RTT
        if comm_type in ("allreduce", "reduce"):
            # reduce-to-root and allreduce are the same XLA op on ICI (the
            # root discard is free); keep both names for surface parity
            return jax.lax.psum(x, axis)
        if comm_type == "broadcast":
            idx = jax.lax.axis_index(axis)
            return jax.lax.psum(jnp.where(idx == 0, x, jnp.zeros_like(x)),
                                axis)
        if comm_type == "allgather":
            return jax.lax.all_gather(x, axis, tiled=True)
        if comm_type == "reduce_scatter":
            return jax.lax.psum_scatter(x, axis, tiled=True)
        if comm_type == "p2p":
            # neighbor ring hop — the pipeline send/recv pattern
            n = jax.lax.axis_size(axis)
            return jax.lax.ppermute(x, axis,
                                    [(i, (i + 1) % n) for i in range(n)])
        raise ValueError(comm_type)

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                               out_specs=spec, check_vma=False))
    return fn, spec


def _bus_factor(comm_type: str, n: int) -> float:
    """Ring-algorithm bus-bandwidth factor (bytes on the wire per payload
    byte): allreduce 2(n-1)/n, allgather/reduce_scatter (n-1)/n,
    broadcast/reduce (n-1)/n."""
    if n <= 1:
        return 0.0
    if comm_type == "allreduce":
        return 2.0 * (n - 1) / n
    if comm_type == "p2p":
        return 1.0  # every byte crosses exactly one link
    return float(n - 1) / n


def collective_perf(comm_type: str, round: int = 50,
                    size_and_time: dict | None = None, mesh: Mesh | None = None,
                    max_nbytes: int = 1 << 26) -> list[dict]:
    """Run the bandwidth sweep for ``comm_type``; returns one row per size:
    ``{"nbytes", "seconds_per_iter", "bus_gbps", "axis", "participants",
    "over_threshold"}`` and logs a table (warning when a threshold from
    ``size_and_time`` — {nbytes: max_seconds} — is exceeded, matching the
    reference's Perf Warning contract).

    Without ``size_and_time`` the sweep runs 1MB → min(1GB, max_nbytes)
    (the reference sweeps to 1GB; ``max_nbytes`` defaults to 64MB so a CI
    mesh of virtual CPU devices finishes in seconds — pass 1 << 30 on real
    hardware for the full reference sweep)."""
    if comm_type not in _COMM_TYPES:
        raise ValueError(
            f"comm_type must be one of {_COMM_TYPES}, got {comm_type!r}")
    if mesh is None:
        from . import get_hybrid_parallel_mesh

        mesh = get_hybrid_parallel_mesh()
        if mesh is None:
            devs = np.asarray(jax.devices())
            mesh = Mesh(devs.reshape(-1), axis_names=("dp",))
    shape = dict(mesh.shape)
    axis = _axis_for(comm_type, shape)
    n = shape.get(axis, 1) if axis else 1
    fn, spec = _build_op(comm_type, mesh, axis)
    sizes = (sorted(int(s) for s in size_and_time) if size_and_time
             else [1 << p for p in range(20, max(21, max_nbytes.bit_length()))
                   if (1 << p) <= max_nbytes])
    rows = []
    for nbytes in sizes:
        elems = max(nbytes // 4, n)
        elems -= elems % n  # divisible for scatter/gather tiling
        x = jax.device_put(jnp.zeros((elems,), jnp.float32),
                           NamedSharding(mesh, spec))
        # barrier = fetch of a DEVICE-SIDE 1-element slice (4 bytes over the
        # host link) — fetching the full payload would attribute host-link
        # time to the collective and corrupt the ICI signature
        np.asarray(fn(x)[0:1])  # warmup + compile, fetch-barriered
        t0 = time.perf_counter()
        out = None
        for _ in range(round):
            out = fn(x)
        np.asarray(out[0:1])  # ONE tiny fetch barrier after the burst
        sec = (time.perf_counter() - t0) / round
        gbps = _bus_factor(comm_type, n) * elems * 4 / sec / 1e9
        thresh = (size_and_time or {}).get(nbytes)
        over = thresh is not None and thresh > -1 and sec > thresh
        rows.append({"nbytes": elems * 4, "seconds_per_iter": sec,
                     "bus_gbps": round_(gbps), "axis": axis,
                     "participants": n, "over_threshold": over})
        msg = (f"[{comm_type.title()}Test] nbytes {elems * 4}B "
               f"axis={axis} n={n}: {sec:.6f} s/iter, "
               f"bus {gbps:.2f} GB/s")
        logger.info(msg)
        if over:
            logger.warning(f"[Perf Warning] {comm_type.title()} Test "
                           f"Timeout! {sec} > {thresh}")
    return rows


def round_(v: float) -> float:
    return float(f"{v:.4g}")
