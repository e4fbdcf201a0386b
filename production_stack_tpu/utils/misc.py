"""Shared helpers: singleton metaclass, URL validation, ulimit, parsers.

Capability parity with reference src/vllm_router/utils.py (SingletonMeta
:10-38, validate_url :41-60, set_ulimit :63-79, static list parsers :82-95);
re-designed with explicit reset support for tests and hot reconfiguration.
"""

import os
import re
import resource
from abc import ABCMeta
from typing import Any, Dict, List, Optional

from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)


class SingletonMeta(type):
    """Metaclass giving each class a single process-wide instance.

    Unlike a naive implementation, instances can be explicitly dropped
    (``Cls.reset_instance()``) so dynamic reconfiguration and tests can
    rebuild singletons without process restarts.
    """

    _instances: Dict[type, Any] = {}

    def __call__(cls, *args, **kwargs):
        if cls not in cls._instances:
            cls._instances[cls] = super().__call__(*args, **kwargs)
        return cls._instances[cls]

    def instance_or_none(cls) -> Optional[Any]:
        return cls._instances.get(cls)

    def has_instance(cls) -> bool:
        return cls in cls._instances

    def reset_instance(cls) -> None:
        cls._instances.pop(cls, None)


class SingletonABCMeta(ABCMeta, SingletonMeta):
    """Singleton + ABC combined (for abstract service-discovery bases)."""


_URL_RE = re.compile(r"^(https?)://([\w.-]+)(:\d+)?(/.*)?$")


def validate_url(url: str) -> bool:
    return bool(_URL_RE.match(url))


def set_ulimit(target_soft: int = 65535) -> None:
    """Raise RLIMIT_NOFILE soft limit for high-concurrency streaming."""
    try:
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft < target_soft:
            resource.setrlimit(
                resource.RLIMIT_NOFILE, (min(target_soft, hard), hard)
            )
    except (ValueError, OSError) as e:
        logger.warning("could not raise RLIMIT_NOFILE: %s", e)


def parse_comma_separated(value: Optional[str]) -> List[str]:
    if not value:
        return []
    return [v.strip() for v in value.split(",") if v.strip()]


def parse_static_urls(static_backends: str) -> List[str]:
    urls = parse_comma_separated(static_backends)
    bad = [u for u in urls if not validate_url(u)]
    if bad:
        raise ValueError(f"invalid backend URLs: {bad}")
    return urls


def parse_static_model_types(value: Optional[str]) -> List[str]:
    return parse_comma_separated(value)


def parse_static_aliases(value: Optional[str]) -> Dict[str, str]:
    """Parse "alias1:model1,alias2:model2" into a dict."""
    aliases: Dict[str, str] = {}
    for pair in parse_comma_separated(value):
        if ":" not in pair:
            raise ValueError(f"invalid alias spec {pair!r}, expected alias:model")
        alias, model = pair.split(":", 1)
        aliases[alias.strip()] = model.strip()
    return aliases


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives for this checkout:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.

    The directory is part of every cache key, so it must never move:
    no temporary name, pid or timestamp goes into it, and every process
    started from one checkout (engine children included) resolves the
    same path. Imports nothing from JAX — launchers call it to report
    the cache without touching the chip."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".jax_cache"))


def place_compile_cache() -> str:
    """Give the persistent compilation cache its home before the first
    compile; entry points that build executables call this first.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it by itself
    and nothing is set in code. Only where it is unset is the fixed
    in-checkout directory configured. Returns the directory in use."""
    path = compile_cache_dir()
    if path != os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
