"""Model zoo + registry.

The reference's "zoo" is one model reached three ways (first-party TF
graph builder, ``keras.applications.resnet50``, ``torchvision resnet50``
— SURVEY.md §2). Here one registry serves every front-end; BASELINE.json
additionally calls for EfficientNet-B4 and ViT-B/16 configs.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax.numpy as jnp

from distributeddeeplearning_tpu.models import decoder as _decoder_specs
from distributeddeeplearning_tpu.models.efficientnet import EfficientNet
from distributeddeeplearning_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
    ResNet200,
    resnet_v1,
)
from distributeddeeplearning_tpu.models.transformer_lm import TransformerLM
from distributeddeeplearning_tpu.models.vit import ViT

_REGISTRY: Dict[str, Callable[..., Any]] = {}
_ATTENTION_MODELS: set = set()
_MOE_MODELS: set = set()
_REMAT_MODELS: set = set()


def register_model(
    name: str,
    factory: Callable[..., Any],
    *,
    attention: bool = False,
    moe: bool = False,
    remat: bool = False,
) -> None:
    _REGISTRY[name.lower()] = factory
    if attention:
        _ATTENTION_MODELS.add(name.lower())
    if moe:
        _MOE_MODELS.add(name.lower())
    if remat:
        _REMAT_MODELS.add(name.lower())


def get_model(
    name: str,
    *,
    num_classes: int = None,
    dtype=jnp.bfloat16,
    attn_impl: str = None,
    moe_experts: int = None,
    remat: bool = None,
    **kw,
):
    """Instantiate a model by name (e.g. ``"resnet50"``).

    ``num_classes=None`` keeps each family's own default (1000 ImageNet
    classes for the vision zoo, 32k vocab for the LMs — forcing one
    global default would silently shrink an LM's vocab). ``dtype`` may
    be a jnp dtype or a string (``TrainConfig.compute_dtype``, e.g.
    ``"bfloat16"``/``"float32"`` — the compute dtype of the forward
    pass; params stay float32 either way). ``attn_impl``
    (``TrainConfig.attn_impl``: auto/xla/pallas/fused/ring) is forwarded
    to models registered with attention support and ignored for conv
    models; ``None`` keeps the model's own default (``"auto"``).
    """
    key = name.lower()
    if key not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    if isinstance(dtype, str):
        dtype = jnp.dtype(dtype)
    if attn_impl is not None and key in _ATTENTION_MODELS:
        kw["attn_impl"] = attn_impl
    if moe_experts is not None and key in _MOE_MODELS:
        kw["moe_experts"] = moe_experts
    if remat is not None and key in _REMAT_MODELS:
        kw["remat"] = remat
    if num_classes is not None:
        kw["num_classes"] = num_classes
    return _REGISTRY[key](dtype=dtype, **kw)


def available_models():
    return sorted(_REGISTRY)


for _depth in (18, 34, 50, 101, 152, 200):
    register_model(
        f"resnet{_depth}",
        (lambda d: (lambda num_classes=1000, dtype=jnp.bfloat16, **kw: ResNet(
            depth=d, num_classes=num_classes, dtype=dtype, **kw)))(_depth),
    )

# ViT family (BASELINE.json config: ViT-B/16). Name = vit_<variant><patch>.
for _variant in ("ti", "s", "b", "l", "h"):
    register_model(
        f"vit_{_variant}16",
        (lambda v: (lambda num_classes=1000, dtype=jnp.bfloat16, **kw: ViT(
            variant=v, patch_size=16, num_classes=num_classes, dtype=dtype,
            **kw)))(_variant),
        attention=True,
        remat=True,
    )

# Decoder-only LM family (long-context tier; num_classes = vocab size).
for _v in ("tiny", "small", "base", "large"):
    register_model(
        f"lm_{_v}",
        (lambda v: (lambda num_classes=32_000, dtype=jnp.bfloat16, **kw: TransformerLM(
            variant=v, vocab_size=num_classes, dtype=dtype, **kw)))(_v),
        attention=True,
        moe=True,  # dense by default; MOE_EXPERTS turns on routed FFNs
        remat=True,
    )
    # MoE variant (expert-parallel tier, models/moe.py): every 2nd block's
    # FFN routed over 8 experts by default; override via moe_experts=...
    register_model(
        f"lm_moe_{_v}",
        (lambda v: (
            lambda num_classes=32_000, dtype=jnp.bfloat16, moe_experts=8, **kw:
            TransformerLM(
                variant=v, vocab_size=num_classes, dtype=dtype,
                moe_experts=moe_experts, **kw)))(_v),
        attention=True,
        moe=True,
        remat=True,
    )

# Decoders built from a layer spec (models/decoder.py): keyword
# arguments beyond the usual ones state the run's share of the spec
# (`layers`, `experts_held`, `first_expert`).
for _name in _decoder_specs.SPECS:
    register_model(
        _name,
        (lambda n: (lambda num_classes=32_000, dtype=jnp.bfloat16, **kw:
                    _decoder_specs.build(
                        n, num_classes=num_classes, dtype=dtype, **kw)))(_name),
        attention=True,
        remat=True,
    )

# EfficientNet family (BASELINE.json config: EfficientNet-B4).
for _b in range(8):
    register_model(
        f"efficientnet_b{_b}",
        (lambda v: (lambda num_classes=1000, dtype=jnp.bfloat16, **kw: EfficientNet(
            variant=v, num_classes=num_classes, dtype=dtype, **kw)))(f"b{_b}"),
    )

__all__ = [
    "EfficientNet",
    "ViT",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "ResNet200",
    "resnet_v1",
    "get_model",
    "register_model",
    "available_models",
]
