"""Collective/compute overlap flag pack for the TPU XLA/libtpu runtime.

"Scalable Training of Language Models using JAX pjit and TPUv4" (PAPERS.md)
attributes multichip efficiency to sharding annotations *plus* XLA's
latency-hiding scheduler: the compiler splits each collective into an async
start/done pair and schedules independent compute between them. On current
libtpu that scheduler and the async collective lowering are controlled by
flags, consumed from the LIBTPU_INIT_ARGS environment variable at backend
initialization — the same pack production JAX trainers (MaxText et al.) ship.

What each flag buys the data-parallel/ZeRO-1 step (parallel/zero.py):

  - async_collective_fusion(+fuse_all_gather, +multiple_steps): the gradient
    reduce-scatter and the post-update param all-gather become async pairs
    that XLA fuses into neighbouring compute regions instead of serial
    barriers at the end of the step;
  - overlap_compute_collective_tc + latency-hiding scheduling: the
    TensorCore keeps executing (e.g. the next microbatch's backward under
    grad accumulation) while ICI traffic is in flight;
  - data_parallel_all_reduce_opt / different_sized_ops: the classic DP
    gradient-bucket reorderings, still profitable for the per-tensor
    collectives the unstacked per-layer layout (round 6) produces — each
    layer's params are separate leaves, so under fsdp the all-gathers are
    layer-granular and the scheduler can prefetch layer i+1's gather behind
    layer i's compute.

These are libtpu flags: on CPU/GPU backends LIBTPU_INIT_ARGS is simply never
read, so applying the pack is a safe no-op off-TPU (the CPU-mesh tests run
with it applied). Must be called BEFORE the first
jax device/backend touch in the process; importing jax is fine, initializing
the backend is not.
"""

from __future__ import annotations

import os
from typing import List, MutableMapping, Optional

OVERLAP_FLAG_PACK = (
    "--xla_tpu_enable_data_parallel_all_reduce_opt=true",
    "--xla_tpu_data_parallel_opt_different_sized_ops=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
)

_ENV_VAR = "LIBTPU_INIT_ARGS"


def _flag_name(flag: str) -> str:
    return flag.split("=", 1)[0]


def apply_overlap_flags(env: Optional[MutableMapping[str, str]] = None
                        ) -> List[str]:
    """Append the overlap pack to LIBTPU_INIT_ARGS; returns what was added.

    Flags whose name the user already set (either polarity) are left alone —
    an operator's explicit choice wins over the pack. Idempotent.
    """
    if env is None:
        env = os.environ
    existing = env.get(_ENV_VAR, "")
    present = {_flag_name(f) for f in existing.split() if f}
    added = [f for f in OVERLAP_FLAG_PACK if _flag_name(f) not in present]
    if added:
        env[_ENV_VAR] = " ".join(([existing] if existing else []) + added)
    return added


def overlap_flags_active(env: Optional[MutableMapping[str, str]] = None
                         ) -> bool:
    """True when every flag in the pack is present (any polarity counts as
    'operator decided')."""
    if env is None:
        env = os.environ
    present = {_flag_name(f) for f in env.get(_ENV_VAR, "").split() if f}
    return all(_flag_name(f) in present for f in OVERLAP_FLAG_PACK)


def pack_state(env: Optional[MutableMapping[str, str]] = None) -> dict:
    """Provenance view of the runtime flag state (telemetry/provenance.py):
    the full LIBTPU_INIT_ARGS value plus which pack flags are present —
    enough to reproduce the collective-overlap configuration of a run from
    its log header alone."""
    if env is None:
        env = os.environ
    value = env.get(_ENV_VAR, "")
    present = {_flag_name(f) for f in value.split() if f}
    n_present = sum(
        1 for f in OVERLAP_FLAG_PACK if _flag_name(f) in present)
    return {
        "libtpu_init_args": value,
        # active == every pack flag present (overlap_flags_active semantics)
        "overlap_pack_active": n_present == len(OVERLAP_FLAG_PACK),
        "overlap_pack_present": n_present,
        "overlap_pack_size": len(OVERLAP_FLAG_PACK),
    }
