from production_stack_tpu.utils.log import init_logger
from production_stack_tpu.utils.misc import (
    SingletonMeta,
    compile_cache_dir,
    parse_comma_separated,
    parse_static_aliases,
    parse_static_model_types,
    parse_static_urls,
    place_compile_cache,
    set_ulimit,
    validate_url,
)

__all__ = [
    "init_logger",
    "compile_cache_dir",
    "place_compile_cache",
    "SingletonMeta",
    "validate_url",
    "set_ulimit",
    "parse_comma_separated",
    "parse_static_aliases",
    "parse_static_model_types",
    "parse_static_urls",
]
