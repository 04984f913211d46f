"""Task registry — maps ``model_config.model_type`` to a task factory.

Parity target: the reference's dynamic plugin loader
(``experiments/__init__.py:8-43`` + ``utils/dataloaders_utils.py:16-23``,
which ``SourceFileLoader``-import ``experiments/<task>/model.py`` and look up
the class named by ``model_type``).  Here built-in tasks register by name;
external plugins can either call :func:`register_task` or provide a
``model_folder`` with a ``task.py`` exposing ``make_task(model_config)``.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Callable, Dict

from .base import BaseTask

TASK_REGISTRY: Dict[str, Callable[[Any], BaseTask]] = {}


def register_task(name: str):
    def deco(factory: Callable[[Any], BaseTask]):
        TASK_REGISTRY[name] = factory
        return factory
    return deco


def _apply_plugin_config(model_config, folder: str) -> None:
    """Model-specific config discovery (reference ``core/config.py:100-116``):
    a ``config.py`` in the model folder may define ``<model_type>Config``
    whose attributes/defaults are merged into the model config (explicit
    YAML keys win)."""
    cfg_path = os.path.join(folder, "config.py")
    if not os.path.exists(cfg_path):
        return
    spec = importlib.util.spec_from_file_location("flute_tpu_plugin_cfg",
                                                  cfg_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # type: ignore[union-attr]
    cls = getattr(mod, model_config.get("model_type", "LR") + "Config", None)
    if cls is None:
        return
    defaults = getattr(cls, "defaults", None)
    if defaults is None:
        defaults = {k: v for k, v in vars(cls).items()
                    if not k.startswith("_") and not callable(v)}
    for key, value in defaults.items():
        if model_config.get(key) is None:
            model_config[key] = value


def make_task(model_config) -> BaseTask:
    """Instantiate the task named by ``model_config.model_type``."""
    model_type = model_config.get("model_type", "LR")
    folder = model_config.get("model_folder")
    if folder:
        _apply_plugin_config(model_config, folder)
        plugin = os.path.join(folder, "task.py")
        if os.path.exists(plugin):
            spec = importlib.util.spec_from_file_location("flute_tpu_plugin", plugin)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)  # type: ignore[union-attr]
            return mod.make_task(model_config)
    if model_type not in TASK_REGISTRY:
        _load_builtins()
    if model_type not in TASK_REGISTRY:
        raise KeyError(
            f"unknown model_type {model_type!r}; known: {sorted(TASK_REGISTRY)}")
    return TASK_REGISTRY[model_type](model_config)


def _load_builtins() -> None:
    from . import cv  # noqa: F401  (registers on import)
    for name, factory in {
        "LR": cv.make_lr_task,
        "CNN": cv.make_cnn_femnist_task,
        "CNN_FEMNIST": cv.make_cnn_femnist_task,
        "CIFAR_CNN": cv.make_cifar_cnn_task,
    }.items():
        TASK_REGISTRY.setdefault(name, factory)
    try:
        from . import resnet
        TASK_REGISTRY.setdefault("RESNET", resnet.make_resnet_task)
        TASK_REGISTRY.setdefault("ResNet", resnet.make_resnet_task)
    except ImportError:
        pass
    try:
        from . import nlp
        TASK_REGISTRY.setdefault("RNN", nlp.make_shakespeare_lstm_task)
        TASK_REGISTRY.setdefault("LSTM", nlp.make_shakespeare_lstm_task)
        TASK_REGISTRY.setdefault("GRU", nlp.make_gru_lm_task)
    except ImportError:
        pass
    try:
        from . import ecg
        TASK_REGISTRY.setdefault("ECG_CNN", ecg.make_ecg_task)
    except ImportError:
        pass
    try:
        from . import bert
        TASK_REGISTRY.setdefault("BERT", bert.make_bert_mlm_task)
    except ImportError:
        pass
    try:
        from . import fednewsrec
        TASK_REGISTRY.setdefault("NRMS", fednewsrec.make_fednewsrec_task)
        TASK_REGISTRY.setdefault("FEDNEWSREC", fednewsrec.make_fednewsrec_task)
    except ImportError:
        pass
    try:
        from . import ringlm
        TASK_REGISTRY.setdefault("RINGLM", ringlm.make_ringlm_task)
    except ImportError:
        pass
    try:
        from . import lfm2
        TASK_REGISTRY.setdefault("LFM2_MOE", lfm2.make_lfm2_task)
    except ImportError:
        pass
    try:
        from . import mla_moe
        TASK_REGISTRY.setdefault("MLA_MOE", mla_moe.make_mla_moe_task)
    except ImportError:
        pass
    try:
        from . import sdar_moe
        TASK_REGISTRY.setdefault("SDAR_MOE", sdar_moe.make_sdar_moe_task)
    except ImportError:
        pass
    try:
        from . import laguna
        TASK_REGISTRY.setdefault("LAGUNA_MOE", laguna.make_laguna_moe_task)
    except ImportError:
        pass
