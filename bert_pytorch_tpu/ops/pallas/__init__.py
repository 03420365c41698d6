"""Pallas TPU kernels — the framework's replacement for the reference's
native CUDA dependencies (SURVEY §2.3):

  layernorm.py        <- apex FusedLayerNormAffineFunction (modeling.py:303)
  flash_attention.py  <- (no reference equivalent; the TPU-correct way to run
                         the attention inner loop without materializing SxS)
  kda.py              <- (no reference equivalent; the chunks of the gated
                         delta-rule recurrence of ops/kda.py with the carried
                         state in VMEM, imported where ops/kda.py takes them)

apex's amp_C multi_tensor_lamb / FusedLAMB (optimization.py:27-33,
run_squad.py:703-725) has no kernel here: XLA fuses optim/lamb.py's update a
leaf, and the CUDA kernels existed mainly because torch eager launched one
kernel per tensor.

Every kernel has an interpret-mode path so the test suite exercises the same
code on CPU; on-device compilation happens only on TPU backends.
"""

from bert_pytorch_tpu.ops.pallas.layernorm import layer_norm_pallas  # noqa: F401
from bert_pytorch_tpu.ops.pallas.flash_attention import flash_attention  # noqa: F401
