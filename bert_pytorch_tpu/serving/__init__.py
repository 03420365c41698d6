"""Inference & serving stack: checkpoints -> traffic (ROADMAP item 1).

Three layers, each usable on its own:

- `serving.engine`   — restore params from a checkpoint (either encoder
  layout), AOT lower/compile the task forward for a small set of bucketed
  sequence lengths so steady-state traffic never recompiles.
- `serving.batcher`  — bounded request queue + continuous-batching
  scheduler that PACKS multiple short requests into one row using the
  training packer (data/packing.first_fit) + segment-aware attention,
  demuxing per-segment outputs back to their requests.
- `serving.frontend` — stdlib HTTP server: POST /v1/{squad,ner} plus the
  Prometheus /metrics and /healthz every training phase already serves,
  wired through telemetry.init_run(phase="serve").

`run_server.py` at the repo root assembles them; tools/loadtest.py drives
them; docs/SERVING.md is the operator guide.
"""

from bert_pytorch_tpu.serving.batcher import (  # noqa: F401
    InferenceRequest, Overloaded, RequestTimeout, Scheduler, TooLong)
from bert_pytorch_tpu.serving.engine import (  # noqa: F401
    ServingEngine, restore_serving_params, select_bucket)
