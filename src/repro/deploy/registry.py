"""String-keyed deployment-backend registry.

``MemhdModel.deploy(target=..., **backend_opts)`` is a thin dispatch
through this table: a backend is a factory ``(model, **opts) ->
DeployedArtifact`` registered under a target name. The built-in
backends — ``"packed"`` / ``"unpacked"`` (``repro.deploy.digital``) and
``"imc"`` (``repro.imcsim.deploy``) — self-register on first lookup;
future multi-bit or remote backends register the same way:

    from repro.deploy import register_backend

    @register_backend("packed2b")
    def deploy_packed2b(model, *, ...):
        return Packed2bArtifact(...)

Built-ins load lazily (inside ``_ensure_builtins``) so this module —
and through it the padding utilities the kernel callers import — never
drags ``repro.core`` / ``repro.imcsim`` into an import cycle.
"""
from __future__ import annotations

import importlib
from typing import Callable, Dict, Tuple

_BACKENDS: Dict[str, Callable] = {}

# Modules whose import registers the built-in backends.
_BUILTIN_MODULES = ("repro.deploy.digital", "repro.deploy.frontend",
                    "repro.deploy.hierarchical", "repro.deploy.multibit",
                    "repro.imcsim.deploy")


def register_backend(name: str) -> Callable[[Callable], Callable]:
    """Decorator: register a deployment factory under ``name``."""

    def deco(factory: Callable) -> Callable:
        prev = _BACKENDS.get(name)
        if prev is not None and prev is not factory:
            raise ValueError(f"deploy backend {name!r} already registered "
                             f"(by {prev.__module__}.{prev.__qualname__})")
        _BACKENDS[name] = factory
        return factory

    return deco


def _ensure_builtins() -> None:
    for mod in _BUILTIN_MODULES:
        importlib.import_module(mod)


def available_backends() -> Tuple[str, ...]:
    """Registered target names, sorted."""
    _ensure_builtins()
    return tuple(sorted(_BACKENDS))


def get_backend(name: str) -> Callable:
    _ensure_builtins()
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown deploy target {name!r}; registered backends: "
            f"{', '.join(sorted(_BACKENDS))}") from None


def deploy(model, target: str = "packed", **opts):
    """Freeze ``model`` into the serving artifact of backend ``target``."""
    return get_backend(target)(model, **opts)
