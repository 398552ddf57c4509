"""Model registry — the seam that makes the round engine model-agnostic.

A federated workload is a :class:`ModelDef`: how to initialize one client's
TRAINABLE state, compute its local loss, and evaluate a model on the held-out
set. The engine (``repro.core.engine``) dispatches on the TYPE of the frozen,
hashable model config riding in ``EngineConfig.model_cfg`` — the config
object itself stays the cache key for every compiled program and shared
engine, so registering new workloads cannot perturb existing keys or
numerics: ``CNNConfig`` configs resolve to the exact same ``init_cnn`` /
``cnn_loss`` function objects the engine used when it was CNN-hardwired.

Two registries live here:

* config-type -> :class:`ModelDef` (``model_def_for``): the engine-side
  dispatch. Keyed by type so it needs no strings on the hot path.
* workload name -> config builder (``workload_config``): the spec-side
  dispatch. ``ExperimentSpec(model="tinyllama")`` resolves through this to a
  frozen config object; ``"auto"``/``"cnn"`` stay on the paper-CNN path in
  ``build_experiment`` and never touch this table.

``repro.models.lm`` registers the LoRA LM workloads on import (the package
``__init__`` imports it, so any ``repro.models.registry`` import sees them).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.paper_cnn import CNNConfig
from repro.models.cnn import cnn_forward, cnn_loss, init_cnn


@dataclasses.dataclass(frozen=True)
class ModelDef:
    """One federated workload's model hooks.

    ``init(cfg, key)`` returns the PER-CLIENT trainable pytree — for the
    LoRA LM that is the adapter tree only, so the flat plane is
    ``[N, P_adapter]`` while the frozen base rides outside the plane.
    ``loss(params, batch, cfg)`` consumes ``batch = {"images", "labels"}``
    (LM workloads ride token windows in the ``"images"`` slot).
    ``evaluate(params, test_x, test_y, cfg=cfg)`` returns
    ``(accuracy, per_class)``.
    ``price_uploads=True`` tells the driver to price the fleet's upload
    payload ``z`` from the trainable parameter count (``P·32`` bits) instead
    of the paper CNN's fixed default — the LoRA workloads upload P_adapter,
    never P_base.
    ``make_dataset(cfg, num_samples, seed=...)`` (optional) builds the
    workload's synthetic dataset; ``None`` means the workload rides the
    image datasets selected by ``ExperimentSpec.dataset`` (the CNN path).
    ``frozen(cfg)`` (optional) gives the tree every client shares and none
    trains (a LoRA workload's base). The engine places it on the device
    once per process and config (``repro.core.engine.frozen_params``) and
    hands it to every program as an argument; ``loss`` and ``evaluate``
    then take it as the keyword ``frozen``. ``None``: nothing is frozen,
    and the hooks take no such keyword.
    """
    name: str
    init: Callable
    loss: Callable
    evaluate: Callable
    price_uploads: bool = False
    make_dataset: Any = None
    frozen: Any = None


def _cnn_evaluate(params, test_images, test_labels, *, cfg: CNNConfig):
    logits = cnn_forward(params, test_images, cfg)
    pred = jnp.argmax(logits, axis=-1)
    acc = jnp.mean((pred == test_labels).astype(jnp.float32))
    onehot = jax.nn.one_hot(test_labels, cfg.num_classes)
    correct = (pred == test_labels).astype(jnp.float32)[:, None] * onehot
    per_class = jnp.sum(correct, 0) / jnp.maximum(jnp.sum(onehot, 0), 1.0)
    return acc, per_class


#: the paper's workload — binds the ORIGINAL function objects so every
#: jaxpr the generalized engine traces for a CNNConfig is the one it
#: traced before the registry existed (the model="cnn" bit-identity pin)
CNN_DEF = ModelDef(name="cnn", init=init_cnn, loss=cnn_loss,
                   evaluate=_cnn_evaluate)

_DEFS_BY_CONFIG_TYPE: Dict[type, ModelDef] = {CNNConfig: CNN_DEF}
_WORKLOADS: Dict[str, Callable[[], Any]] = {}


def register_model_def(cfg_type: type, mdef: ModelDef) -> None:
    """Bind a frozen-config TYPE to its engine hooks."""
    _DEFS_BY_CONFIG_TYPE[cfg_type] = mdef


def register_workload(name: str, builder: Callable[[], Any]) -> None:
    """Bind an ``ExperimentSpec.model`` name to a config builder."""
    _WORKLOADS[name] = builder


def model_def_for(model_cfg) -> ModelDef:
    """The :class:`ModelDef` for a config object (engine-side dispatch)."""
    mdef = _DEFS_BY_CONFIG_TYPE.get(type(model_cfg))
    if mdef is None:
        raise TypeError(
            f"no ModelDef registered for config type "
            f"{type(model_cfg).__name__}; register one with "
            "repro.models.registry.register_model_def")
    return mdef


def workload_config(name: str):
    """Resolve an ``ExperimentSpec.model`` name to its frozen config."""
    try:
        builder = _WORKLOADS[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; known: "
            f"{('auto', 'cnn') + workload_names()}") from None
    return builder()


def workload_names() -> Tuple[str, ...]:
    """The registered non-CNN workload names (``"auto"``/``"cnn"`` are
    aliases for the paper CNN and resolve in ``build_experiment``)."""
    return tuple(sorted(_WORKLOADS))
