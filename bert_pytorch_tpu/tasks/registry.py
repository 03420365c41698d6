"""Task registry: one declarative TaskSpec per scenario, every task served.

The reference ships seven task heads (modeling.py:1053-1308) but wires
only two end to end; through round 13 this repo was the same — run_squad
and run_ner each hand-rolled an entry point, and adding a scenario meant
copying one. The registry makes a scenario O(1): register a TaskSpec and
the task automatically gains

- the shared finetune driver (`run_finetune.py --task <name>`, or its
  thin aliases run_squad.py / run_ner.py), with packed training and
  length-bucketed eval (training/finetune.py);
- a `POST /v1/<name>` serving route (run_server.py builds services by
  iterating this registry), AOT bucketed engine forwards
  (serving/engine.py), and the per-segment demux matching the head's
  `output_kind`;
- CI serving coverage: scripts/check_serve.sh diffs the live server's
  task set against `all_tasks()`, so a registered-but-unserved (or
  served-but-unregistered) task fails the gate;
- graph-lint eligibility (tools/graphcheck.py serve/finetune combos
  derive expectations from the specs).

A TaskSpec is data, not subclassing: callables for the model head, loss,
featurizer, predict/decode, metric, and serving service, plus the
serving request schema (docs/TASKS.md documents the contract and the
add-a-task walkthrough).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Mapping, Tuple

_REGISTRY: Dict[str, "TaskSpec"] = {}
_LOADED = False


@dataclass(frozen=True)
class TaskSpec:
    """One registered scenario. Field groups:

    finetune driver —
      `parse_arguments(argv) -> args`: the task's CLI (run_squad/run_ner
      keep their historical flags; new tasks share the driver's base
      parser); `setup(args, config, tel) -> training.finetune.TaskRun`.

    serving —
      `build_serving_model(config, dtype, opts) -> nn.Module` (opts is
      run_server's per-task option dict: labels, class_names,
      max_segments, ...); `forward_builder(model)` the pure fn the
      engine AOT-compiles per bucket (tasks/predict.py builders);
      `make_service(scheduler, tokenizer, opts)` the HTTP handler
      callable; `output_kind` picks the batcher demux — "token" heads
      slice `[row, offset:offset+len]`, "segment" heads index
      `[row, segment]` of per-segment pooled outputs;
      `request_schema` documents the POST body (served on /healthz and
      in docs/TASKS.md).

    bookkeeping —
      `head`: the models/bert.py class; `reference_heads`: the reference
      modeling.py classes this task covers (docs/MIGRATION.md mapping);
      `metric`: the task's headline eval metric name.
    """

    name: str
    title: str
    head: str
    output_kind: str                     # "token" | "segment"
    metric: str
    request_schema: Mapping[str, str]
    parse_arguments: Callable[..., Any]
    setup: Callable[..., Any]
    build_serving_model: Callable[..., Any]
    forward_builder: Callable[[Any], Callable]
    make_service: Callable[..., Callable]
    tokenizer_kind: str = "wordpiece"
    reference_heads: Tuple[str, ...] = ()
    serving_defaults: Mapping[str, Any] = field(default_factory=dict)


def register(spec: TaskSpec) -> TaskSpec:
    if spec.output_kind not in ("token", "segment"):
        raise ValueError(f"task '{spec.name}': output_kind "
                         f"{spec.output_kind!r} not in ('token', 'segment')")
    if spec.name in _REGISTRY:
        raise ValueError(f"task '{spec.name}' already registered")
    _REGISTRY[spec.name] = spec
    return spec


def _ensure_loaded() -> None:
    """Import the built-in task modules (each registers itself on
    import). Lazy so `all_tasks()` works without jax having been
    configured and so task modules can import registry freely."""
    global _LOADED
    if _LOADED:
        return
    # mark loaded only AFTER every module imported: a failed task import
    # must stay loud on every later call, never leave a silently partial
    # registry behind a one-time error
    from bert_pytorch_tpu.tasks import (choice, classify,  # noqa: F401
                                        embed, ner_task, squad_task)
    _LOADED = True


def get(name: str) -> TaskSpec:
    _ensure_loaded()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown task {name!r}; registered: "
                       f"{', '.join(all_tasks())}")


def all_tasks() -> Tuple[str, ...]:
    """Sorted names of every registered task — the single source the
    finetune CLI, run_server, check_serve, and graphcheck iterate."""
    _ensure_loaded()
    return tuple(sorted(_REGISTRY))


def specs() -> Tuple[TaskSpec, ...]:
    _ensure_loaded()
    return tuple(_REGISTRY[n] for n in all_tasks())
