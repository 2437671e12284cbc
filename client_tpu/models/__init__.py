"""Model zoo for the TPU serving engine.

Conformance models (the reference's examples assert exact values against the
server's `simple*` family — e.g. add/sub INT32[16] checks in
/root/reference/src/c++/examples/simple_grpc_infer_client.cc:337):

- ``simple``            — INT32[16] add/sub (batched, dynamic batching)
- ``simple_string``     — BYTES decimal add/sub
- ``simple_identity``   — BYTES passthrough
- ``simple_sequence``   — stateful accumulator (sequence batching)
- ``simple_repeat``     — decoupled/streaming repeat
- ``simple_dyna_sequence`` — sequence + additive correlation-id semantics

Flagship models (BASELINE.json configs): ``resnet50``, ``densenet_onnx``
(DenseNet-121), ``bert_base``, ``ssd_mobilenet_v2_coco_quantized``, plus the
``ensemble_bert`` preprocess→BERT→postprocess pipeline.

All are JAX/flax, bfloat16 on the MXU where it matters.
"""

from __future__ import annotations

from typing import Callable

from client_tpu.engine.model import ModelBackend
from client_tpu.engine.repository import ModelRepository

_REGISTRY: dict[str, Callable[[], ModelBackend]] = {}
_NON_DEFAULT: set[str] = set()  # listed/loadable by name, excluded from "all"


def register_model(name: str, default: bool = True):
    def deco(builder: Callable[[], ModelBackend]):
        _REGISTRY[name] = builder
        if not default:
            _NON_DEFAULT.add(name)
        return builder
    return deco


@register_model("pangu_moe", default=False)
def _pangu_moe() -> ModelBackend:
    """The sparse-expert decoder with a latent cache, at its tiny preset.
    Opt-in, and imported when it is built: a launch of any other model
    imports none of it (models/pangu_moe.py, ops/grouped_matmul.py)."""
    from client_tpu.models.pangu_moe import PanguMoeBackend

    return PanguMoeBackend()


@register_model("kimi_linear", default=False)
def _kimi_linear() -> ModelBackend:
    """The hybrid decoder (a recurrent state beside a latent cache), at its
    tiny preset.  Opt-in, and imported when it is built, as ``pangu_moe``."""
    from client_tpu.models.kimi_linear import KimiLinearBackend

    return KimiLinearBackend()


@register_model("smallthinker", default=False)
def _smallthinker() -> ModelBackend:
    """The decoder with window and global layers (a ring beside whole-context
    rows in one arena), grouped-query heads and ReGLU experts, at its tiny
    preset.  Opt-in, and imported when it is built, as ``pangu_moe``."""
    from client_tpu.models.smallthinker import SmallThinkerBackend

    return SmallThinkerBackend()


@register_model("nemotron_h", default=False)
def _nemotron_h() -> ModelBackend:
    """The decoder whose state-space, attention and expert layers are each a
    block of their own (a state and key/value rows in one arena, un-gated
    squared-ReLU experts), at its tiny preset.  Opt-in, and imported when it
    is built, as ``pangu_moe``."""
    from client_tpu.models.nemotron_h import NemotronHBackend

    return NemotronHBackend()


@register_model("granite_hybrid", default=False)
def _granite_hybrid() -> ModelBackend:
    """The dense decoder with a recurrent state (nine Mamba-2 layers to one
    attention layer, a SwiGLU inside every layer, scalar multipliers on the
    embedding, the residual branches, the scores and a tied head), at its tiny
    preset.  Opt-in, and imported when it is built, as ``pangu_moe``."""
    from client_tpu.models.granite_hybrid import GraniteHybridBackend

    return GraniteHybridBackend()


@register_model("ouro", default=False)
def _ouro() -> ModelBackend:
    """The dense decoder whose layer stack runs four passes over one set of
    weights (a key/value cache for every pass in one arena), at its tiny
    preset.  Opt-in, and imported when it is built, as ``pangu_moe``."""
    from client_tpu.models.ouro import OuroBackend

    return OuroBackend()


@register_model("cohere_moe", default=False)
def _cohere_moe() -> ModelBackend:
    """The parallel-block decoder (window and full layers, one LayerNorm that
    attention, routed and averaged shared experts all read, a tied head), at
    its tiny preset.  Opt-in, and imported when it is built, as
    ``pangu_moe``."""
    from client_tpu.models.cohere_moe import CohereMoeBackend

    return CohereMoeBackend()


def model_names() -> list[str]:
    _import_all()
    return sorted(_REGISTRY)


def build_repository(names: list[str] | None = None,
                     jit: bool = True) -> ModelRepository:
    """Repository with the requested zoo models registered (all by default)."""
    _import_all()
    repo = ModelRepository(jit=jit)
    for name, builder in _REGISTRY.items():
        if names is None:
            if name in _NON_DEFAULT:
                continue
        elif name not in names:
            continue
        repo.register(name, builder)
    return repo


def _import_all() -> None:
    """Import every zoo module so its ``register_model`` calls run.  An
    import error propagates: a module that fails to import (a renamed
    Pallas symbol, say) must not quietly vanish from the zoo."""
    from client_tpu.models import (  # noqa: F401
        bert,
        dlrm,
        ensembles,
        evabyte,
        generate,
        simple,
        ssd,
        vision,
    )
    # Multi-chip serving models live with the parallelism code.
    from client_tpu.parallel import serving  # noqa: F401
