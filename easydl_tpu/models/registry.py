"""Model registry: name → (init_fn, loss_fn, data source) factories.

The trainer is model-agnostic; jobs name a model family + config (the
``model_family`` feature Brain also consumes) and the registry builds the pure
functions the Trainer needs.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

_REGISTRY: Dict[str, Callable[..., "ModelBundle"]] = {}
#: modules under easydl_tpu.models that register factories on import
_MODULES = ("mlp", "resnet", "bert", "gpt", "granite_hybrid", "ouro", "laguna",
            "zaya", "joyai", "nemotron_h", "mellum", "sdar", "phi4flash", "keye",
            "kimi_linear",
            "deepfm")


@dataclass
class ModelBundle:
    """Everything the Trainer needs, as pure functions."""

    name: str
    init_fn: Callable  # rng -> params
    loss_fn: Callable  # (params, batch, rng) -> (loss, aux)
    make_data: Callable  # (global_batch, seed) -> host batch iterator
    eval_fn: Optional[Callable] = None
    param_count_hint: int = 0
    #: training FLOPs per example (fwd+bwd, PaLM appendix-B accounting) —
    #: the MFU numerator (core/mfu.py); 0 = unknown, MFU not reported
    flops_per_sample_hint: float = 0.0


def register_model(name: str):
    def deco(factory: Callable[..., ModelBundle]):
        _REGISTRY[name] = factory
        return factory

    return deco


def get_model(name: str, **kwargs: Any) -> ModelBundle:
    if name not in _REGISTRY:
        # Import-on-demand so registering modules stay lazy.
        import importlib

        for mod in _MODULES:
            try:
                importlib.import_module(f"easydl_tpu.models.{mod}")
            except ImportError:
                pass
        if name not in _REGISTRY:
            raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def list_models() -> list:
    import importlib

    for mod in _MODULES:
        try:
            importlib.import_module(f"easydl_tpu.models.{mod}")
        except ImportError:
            pass
    return sorted(_REGISTRY)
