"""``cifar_cnn`` task: the paper's own backbones on CIFAR-shaped batches.

Model selection rides on the ``family="cnn"`` :class:`ModelConfig` encoding
(``configs/paper_cnns.cnn_model``): ``num_layers`` is the ResNet depth
(6n+2), ``d_model`` the stage-0 width, ``vocab_size`` the class count; a
model named ``"mobilenetv2"`` selects the MobileNetV2 backbone instead.
Both backbones take the experiment's E2-Train settings: SLU gates their
identity-shortcut blocks, PSG their conv (and MobileNetV2's depthwise)
weight gradients.

``model_state`` is the BatchNorm running-stat tree: the loss returns the
EMA-updated tree so ``train=False`` prediction normalizes with learned
statistics — the regression this fixes is pinned in
``tests/test_resnet_scan.py``.
"""
from __future__ import annotations

from typing import Any, Tuple

from repro.core.config import Experiment
from repro.models import resnet as R
from repro.tasks import Task, register


def _is_mobilenet(exp: Experiment) -> bool:
    return exp.model.name == "mobilenetv2"


def _init(key, exp: Experiment) -> Tuple[Any, Any]:
    m = exp.model
    if _is_mobilenet(exp):
        return R.init_mobilenetv2(key, num_classes=m.vocab_size, e2=exp.e2)
    return R.init_resnet(key, m.num_layers, num_classes=m.vocab_size,
                         e2=exp.e2, width=m.d_model)


def _make_loss(exp: Experiment):
    e2, depth = exp.e2, exp.model.num_layers
    if _is_mobilenet(exp):
        def loss(params, model_state, batch, rng):
            return R.mobilenetv2_loss(params, model_state, batch, e2, rng,
                                      train=True)
        return loss

    def loss(params, model_state, batch, rng):
        return R.resnet_loss(params, model_state, batch, depth, e2, rng,
                             train=True)

    return loss


def _make_predict(exp: Experiment):
    e2, depth = exp.e2, exp.model.num_layers
    if _is_mobilenet(exp):
        def predict(params, model_state, batch):
            logits, _, _ = R.mobilenetv2_fwd(params, model_state,
                                             batch["image"], e2, train=False)
            return logits
        return predict

    def predict(params, model_state, batch):
        logits, _, _ = R.resnet_fwd(params, model_state, batch["image"],
                                    depth, e2, train=False)
        return logits

    return predict


def _cost(exp: Experiment):
    from repro.core.cost import cnn_cost
    return cnn_cost(exp.model)


CIFAR_CNN_TASK = register(Task(name="cifar_cnn", init=_init,
                               make_loss=_make_loss,
                               make_predict=_make_predict, cost=_cost,
                               depthwise=_is_mobilenet))
