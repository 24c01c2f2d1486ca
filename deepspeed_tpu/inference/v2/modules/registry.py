"""Interface registry for serving modules (reference:
inference/v2/modules/module_registry.py ``DSModuleRegistryBase`` +
interfaces/{attention,linear,moe,embedding,norms,unembed}_base).
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

#: the reference's six module interfaces (SURVEY §2.5)
INTERFACES = ("attention", "linear", "moe", "embedding", "norm", "unembed")


class DSModuleRegistry:
    _registry: Dict[Tuple[str, str], Callable] = {}
    _builtins_loaded = False
    _loading = False

    @classmethod
    def _ensure_builtins(cls) -> None:
        """Built-ins register LAZILY on first use: the implementations live
        across the framework (kernels, MoE, model families) and eager
        import-time registration would pull all of it in just to import
        this module.  The flag latches only on SUCCESS so a transient
        import failure surfaces again instead of an empty registry; the
        _loading sentinel lets _register_builtins itself call register()."""
        if not cls._builtins_loaded and not cls._loading:
            cls._loading = True
            try:
                _register_builtins()
                cls._builtins_loaded = True
            finally:
                cls._loading = False

    @classmethod
    def register(cls, interface: str, name: str, impl: Callable,
                 _builtin: bool = False) -> None:
        if interface not in INTERFACES:
            raise ValueError(f"unknown interface {interface!r}; "
                             f"known: {INTERFACES}")
        if _builtin:
            # deferred builtin load must never clobber a user registration
            cls._registry.setdefault((interface, name), impl)
        else:
            cls._registry[(interface, name)] = impl

    @classmethod
    def get(cls, interface: str, name: str) -> Callable:
        cls._ensure_builtins()
        key = (interface, name)
        if key not in cls._registry:
            avail = [n for (i, n) in cls._registry if i == interface]
            raise KeyError(f"no {interface!r} implementation {name!r}; "
                           f"available: {avail}")
        return cls._registry[key]

    @classmethod
    def list(cls, interface: str = None):
        cls._ensure_builtins()
        return sorted(n for (i, n) in cls._registry
                      if interface is None or i == interface)


def register_module(interface: str, name: str):
    """Decorator: ``@register_module("attention", "paged")``."""
    def deco(impl):
        DSModuleRegistry.register(interface, name, impl)
        return impl

    return deco


def get_module(interface: str, name: str) -> Callable:
    return DSModuleRegistry.get(interface, name)


def list_modules(interface: str = None):
    return DSModuleRegistry.list(interface)


# --------------------------------------------------------------------- #
# Built-in implementations (reference implementations/ dirs)
# --------------------------------------------------------------------- #
def _register_builtins():
    import jax
    import jax.numpy as jnp

    from ....models.transformer import rms_norm
    from ..kernels.ragged_ops import ragged_paged_attention
    from ..kernels.page_ops import _attend_gather

    DSModuleRegistry.register("attention", "paged", ragged_paged_attention,
                              _builtin=True)
    DSModuleRegistry.register("attention", "gather", _attend_gather, _builtin=True)

    DSModuleRegistry.register(
        "linear", "dense",
        lambda x, p: (x @ p["kernel"]) + p.get("bias", 0), _builtin=True)

    from ....moe.sharded_moe import moe_mlp_block

    DSModuleRegistry.register("moe", "sparse", moe_mlp_block, _builtin=True)

    DSModuleRegistry.register(
        "embedding", "lookup",
        lambda tokens, p: jnp.take(p["embedding"], tokens, axis=0), _builtin=True)

    DSModuleRegistry.register("norm", "rmsnorm", rms_norm, _builtin=True)
    from ....models.families import layer_norm

    DSModuleRegistry.register("norm", "layernorm", layer_norm, _builtin=True)

    DSModuleRegistry.register(
        "unembed", "tied",
        lambda h, p: h @ p["embedding"].T, _builtin=True)
    DSModuleRegistry.register(
        "unembed", "lm_head",
        lambda h, p: h @ p["kernel"], _builtin=True)
