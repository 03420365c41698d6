"""Pallas TPU kernels — the framework's replacement for the reference's
native CUDA dependencies (SURVEY §2.3):

  layernorm.py        <- apex FusedLayerNormAffineFunction (modeling.py:303)
  flash_attention.py  <- (no reference equivalent; the TPU-correct way to run
                         the attention inner loop without materializing SxS)
  fused_optim.py      <- apex amp_C multi_tensor_lamb stage1+2 / FusedLAMB
                         (optimization.py:27-33, run_squad.py:703-725)
  kda.py              <- (no reference equivalent; the chunks of the gated
                         delta-rule recurrence of ops/kda.py with the carried
                         state in VMEM, imported where ops/kda.py takes them)

History note on fused_optim: earlier rounds deliberately skipped a
multi-tensor update kernel — measured on v5e (BERT-Large, batch 48) the
jitted optax LAMB + global-norm chain ran within ~30% of the ~11.4 ms
HBM-bandwidth floor, and the CUDA kernels existed mainly because torch
eager launched one kernel per tensor. That measurement was of the
REPLICATED update. Under ZeRO-1 the update runs on shard-shaped leaves
pinned by sharding constraints, where XLA no longer folds the long tail
of small leaves into the big fusions; the bucketed stage1/stage2 kernels
bound the update to O(buckets) launches (norm reductions stay outside, in
optim/lamb.py / parallel/coalesce.py). Off-TPU an XLA fallback evaluating
the same expressions per leaf — bit-identical to the unfused chain — is
selected automatically; see fused_optim.py's numerics contract for the
few-ulp kernel-vs-fallback bound.

Every kernel has an interpret-mode path so the test suite exercises the same
code on CPU; on-device compilation happens only on TPU backends.
"""

from bert_pytorch_tpu.ops.pallas.layernorm import layer_norm_pallas  # noqa: F401
from bert_pytorch_tpu.ops.pallas.flash_attention import flash_attention  # noqa: F401
from bert_pytorch_tpu.ops.pallas.fused_optim import (  # noqa: F401
    lamb_stage1, lamb_stage2)
