"""Serving model-implementation registry (reference:
inference/v2/engine_factory.py:70 policy map → per-arch
``DSTransformerModelBase`` subclasses, model_implementations/*).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional


@dataclasses.dataclass
class ModelImplementation:
    """Policy for serving one HF architecture.

    ``family``: models/hf.py policy name; ``ragged_native``: True when the
    paged-KV ragged engine serves it — that is EVERY buildable family: the
    model object each recipe builds says what it is to the serving path
    (``CausalLM.serving_family`` / ``UniversalCausalLM.serving_family``,
    models/serving.py) and the one paged forward
    (``model_runner.ragged_forward``) serves it.
    """
    arch: str
    family: str
    ragged_native: bool
    notes: str = ""

    def build(self, hf_config: Any, **overrides):
        """HF config → framework model (the make_*_layer factory analogue)."""
        from ....models.hf import from_pretrained_config

        return from_pretrained_config(hf_config, **overrides)

    def convert(self, state_dict: Dict, model) -> Dict:
        from ....models.hf import (
            NATIVE_FAMILIES,
            convert_arch_state_dict,
            convert_llama_state_dict,
        )

        if self.family in NATIVE_FAMILIES:
            return convert_llama_state_dict(state_dict, model.config)
        return convert_arch_state_dict(state_dict, model.config, self.family)


#: per-arch serving notes; arch→family comes from models/hf.py's policy map
#: (single source of truth); every buildable family serves ragged
_NOTES = {
    "Qwen2ForCausalLM": "llama + qkv bias",
    "MixtralForCausalLM": "MoE serving via sparse-slot dispatch",
    "GPT2LMHeadModel": "learned positions + LN",
    "OPTForCausalLM": "learned positions offset 2",
    "BloomForCausalLM": "ALiBi",
    "FalconForCausalLM": "parallel attn / MQA",
    "PhiForCausalLM": "partial rotary, parallel attn",
}


#: families with an end-to-end recipe (config + converter + forward)
_BUILDABLE_FAMILIES = ("llama", "qwen2", "mixtral", "gpt2", "opt", "bloom",
                       "falcon", "phi", "gptj")

_IMPLS: Dict[str, ModelImplementation] = {}


def _ensure_impls() -> Dict[str, ModelImplementation]:
    """Built lazily on first lookup (keeps importing this registry from
    pulling in the whole model stack), derived from models/hf.py's policy
    map; _BUILDABLE_FAMILIES is the one local judgment (which families have
    end-to-end recipes) and is validated against the policy map so a new
    family shows up as a loud assertion, not a silent omission."""
    if not _IMPLS:
        from ....models.hf import _ARCH_POLICIES

        known = set(_ARCH_POLICIES.values())
        unknown = set(_BUILDABLE_FAMILIES) - known
        assert not unknown, f"buildable families not in policy map: {unknown}"
        missing = known - set(_BUILDABLE_FAMILIES)
        assert not missing, (f"families {missing} added to the policy map "
                             f"but not classified here as buildable/not")
        _IMPLS.update({arch: ModelImplementation(
            arch, fam, True, _NOTES.get(arch, ""))
            for arch, fam in _ARCH_POLICIES.items()
            if fam in _BUILDABLE_FAMILIES})
    return _IMPLS


def get_implementation(arch_or_config: Any) -> ModelImplementation:
    """Resolve by HF architecture name or config object."""
    impls = _ensure_impls()
    if isinstance(arch_or_config, str):
        if arch_or_config in impls:
            return impls[arch_or_config]
        raise KeyError(f"no serving implementation for {arch_or_config!r}; "
                       f"known: {sorted(impls)}")
    archs = getattr(arch_or_config, "architectures", None) or []
    for a in archs:
        if a in impls:
            return impls[a]
    from ....models.hf import policy_for

    fam = policy_for(arch_or_config)
    for impl in impls.values():
        if impl.family == fam:
            return impl
    raise KeyError(f"no serving implementation for {archs or fam}")


def list_implementations():
    return sorted(_ensure_impls())
