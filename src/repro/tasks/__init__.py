"""Model-agnostic task registry (DESIGN.md §Tasks).

A :class:`Task` adapts one model family to the shared training stack:
``training/train_step.py`` and ``training/trainer.py`` know nothing about
transformers or CNNs — they resolve the experiment's ``task`` key here and
get back ``init`` / ``loss`` callables.  Everything the stack layers on top
(SMD drops, microbatch accumulation, the PSG sign-vote backward and its
measured ``psg_fallback_ratio`` probe, majority vote, SWA, checkpoint +
resume) therefore works for every registered task unchanged.

Contract:

* ``init(key, exp) -> (params, model_state)``.  ``model_state`` is the
  task's non-trainable buffers (e.g. BatchNorm running statistics), ``None``
  when the task has none.  The optimizer never sees it: the train step
  threads it next to the params and stores it on ``TrainState.model_state``.
* ``make_loss(exp) -> loss(params, model_state, batch, rng)`` returning
  ``(total_loss, (metrics, new_model_state))`` with *scalar* metrics (the
  trainer logs them as floats; microbatch accumulation means them).
* ``make_predict(exp) -> predict(params, model_state, batch)`` — eval-mode
  logits: stored statistics, no RNG, no SLU sampling.
* ``cost(exp) -> CostModel`` — the per-layer op-count model for the
  experiment's architecture (``core/cost.py``): energy accounting resolves
  through here (``cost_model(exp)``), so it prices what actually trains —
  never transformer math for a CNN (DESIGN.md §Energy).
* ``depthwise(exp) -> bool`` — whether the experiment's model runs PSG
  depthwise convs, so the train step reports ``psg_dw_fallback_ratio``
  (absent means none).

Built-in tasks: ``"lm"`` (the generic transformer stack) and ``"cifar_cnn"``
(the paper's ResNet-74/110 + MobileNetV2 backbones).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

from repro.core.config import Experiment
from repro.core.cost import TableCostModel

LossFn = Callable[..., Tuple[Any, Tuple[Dict[str, Any], Any]]]


@dataclass(frozen=True)
class Task:
    name: str
    init: Callable[[Any, Experiment], Tuple[Any, Any]]
    make_loss: Callable[[Experiment], LossFn]
    make_predict: Optional[Callable[[Experiment], Callable]] = None
    cost: Optional[Callable[[Experiment], TableCostModel]] = None
    depthwise: Optional[Callable[[Experiment], bool]] = None


_REGISTRY: Dict[str, Task] = {}


def register(task: Task) -> Task:
    if task.name in _REGISTRY:
        raise ValueError(f"task {task.name!r} already registered")
    _REGISTRY[task.name] = task
    return task


def get_task(name: str) -> Task:
    _ensure_builtin()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown task {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def task_names() -> Tuple[str, ...]:
    _ensure_builtin()
    return tuple(sorted(_REGISTRY))


def cost_model(exp: Experiment) -> TableCostModel:
    """The experiment's per-layer cost model, resolved through its task.

    This is the ONE entry point energy accounting uses to price an
    experiment (core/ledger.py); a task without a cost model cannot be
    priced, and that is an error — not a silent fallback to another
    family's arithmetic.
    """
    task = get_task(exp.task)
    if task.cost is None:
        raise ValueError(f"task {task.name!r} registered no cost model; "
                         "energy accounting cannot price this experiment")
    return task.cost(exp)


def _ensure_builtin() -> None:
    # import for the registration side effect; deferred so that importing
    # repro.tasks never drags in model code the caller doesn't use
    from repro.tasks import cifar_cnn, lm  # noqa: F401
