"""Generate the developer API reference (docs/api/*.md) from source.

The reference repo ships a Sphinx/RTD tree with autodoc API pages for
the router and engine-stats modules (reference docs/source/). This
repo's environments cannot install Sphinx, so this is the same
substance — module docstrings, public classes/functions with their
signatures and docstrings — emitted as plain markdown by the stdlib
(inspect), one page per module, plus an index.

Regenerate after changing public APIs:

    JAX_PLATFORMS=cpu python docs/generate_api.py

CI smoke (tests/test_infra.py) imports this module and generates one
page in-memory, so a module that stops importing or a signature crash
fails the suite, not the next release.
"""

import importlib
import inspect
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# public modules, grouped as the index presents them
MODULES = {
    "Serving engine": [
        "production_stack_tpu.engine.config",
        "production_stack_tpu.engine.engine",
        "production_stack_tpu.engine.scheduler",
        "production_stack_tpu.engine.runner",
        "production_stack_tpu.engine.sampler",
        "production_stack_tpu.engine.block_manager",
        "production_stack_tpu.engine.efficiency",
        "production_stack_tpu.engine.guided",
        "production_stack_tpu.engine.metrics",
        "production_stack_tpu.engine.tokenizer",
        "production_stack_tpu.engine.server",
    ],
    "Request router": [
        "production_stack_tpu.router.app",
        "production_stack_tpu.router.routing",
        "production_stack_tpu.router.service_discovery",
        "production_stack_tpu.router.proxy",
        "production_stack_tpu.router.stats",
        "production_stack_tpu.router.dynamic_config",
        "production_stack_tpu.router.shared_state",
        "production_stack_tpu.router.qos",
        "production_stack_tpu.router.semantic_cache",
        "production_stack_tpu.router.pii",
        "production_stack_tpu.router.disagg",
        "production_stack_tpu.router.feature_gates",
        "production_stack_tpu.router.files_api",
        "production_stack_tpu.router.batches_api",
    ],
    "Autoscaler": [
        "production_stack_tpu.autoscaler.policy",
        "production_stack_tpu.autoscaler.collector",
        "production_stack_tpu.autoscaler.actuator",
        "production_stack_tpu.autoscaler.controller",
    ],
    "Fleet observability": [
        "production_stack_tpu.obsplane.aggregator",
        "production_stack_tpu.obsplane.stitch",
        "production_stack_tpu.obsplane.recorder",
        "production_stack_tpu.obsplane.app",
    ],
    "Models and ops": [
        "production_stack_tpu.models.config",
        "production_stack_tpu.models.llama",
        "production_stack_tpu.models.kv",
        "production_stack_tpu.models.encoder",
        "production_stack_tpu.models.lora",
        "production_stack_tpu.models.quant",
        "production_stack_tpu.ops.attention",
        "production_stack_tpu.ops.pallas_paged",
        "production_stack_tpu.ops.moe",
        "production_stack_tpu.ops.norms",
        "production_stack_tpu.ops.rope",
    ],
    "Parallelism": [
        "production_stack_tpu.parallel.mesh",
        "production_stack_tpu.parallel.sharding",
    ],
    "KV cache tiering": [
        "production_stack_tpu.kvcache.chunks",
        "production_stack_tpu.kvcache.connector",
        "production_stack_tpu.kvcache.protocol",
        "production_stack_tpu.kvcache.server",
        "production_stack_tpu.kvcache.store",
        "production_stack_tpu.kvcache.codec",
        "production_stack_tpu.kvcache.pipeline",
    ],
    "KV memory plane": [
        "production_stack_tpu.kvplane.planner",
        "production_stack_tpu.kvplane.app",
    ],
    "Shared": [
        "production_stack_tpu.protocol",
        "production_stack_tpu.signals",
        "production_stack_tpu.tracing",
        "production_stack_tpu.utils",
        "production_stack_tpu.version",
    ],
}


def _sig(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _doc(obj) -> str:
    d = inspect.getdoc(obj)
    return d.strip() if d else ""


def render_module(modname: str) -> str:
    """One markdown page: module doc, then public classes (with public
    methods) and functions defined IN this module (no re-exports)."""
    mod = importlib.import_module(modname)
    out = [f"# `{modname}`", ""]
    if _doc(mod):
        out += [_doc(mod), ""]

    def defined_here(obj):
        return getattr(obj, "__module__", None) == modname

    classes = [(n, o) for n, o in inspect.getmembers(mod, inspect.isclass)
               if defined_here(o) and not n.startswith("_")]
    funcs = [(n, o) for n, o in inspect.getmembers(mod, inspect.isfunction)
             if defined_here(o) and not n.startswith("_")]

    for name, cls in classes:
        out += [f"## class `{name}{_sig(cls)}`", ""]
        if _doc(cls):
            out += [_doc(cls), ""]
        for mname, meth in inspect.getmembers(cls, inspect.isfunction):
            if mname.startswith("_") or meth.__qualname__.split(".")[0] \
                    != name:
                continue
            out += [f"### `{name}.{mname}{_sig(meth)}`", ""]
            if _doc(meth):
                out += [_doc(meth), ""]
        for pname, prop in inspect.getmembers(
                cls, lambda o: isinstance(o, property)):
            if pname.startswith("_"):
                continue
            out += [f"### property `{name}.{pname}`", ""]
            if _doc(prop):
                out += [_doc(prop), ""]

    for name, fn in funcs:
        out += [f"## `{name}{_sig(fn)}`", ""]
        if _doc(fn):
            out += [_doc(fn), ""]
    return "\n".join(out).rstrip() + "\n"


def main() -> None:
    api_dir = os.path.join(REPO, "docs", "api")
    os.makedirs(api_dir, exist_ok=True)
    index = ["# API reference", "",
             "Generated from source docstrings by `docs/generate_api.py`",
             "(stdlib-inspect equivalent of the reference's Sphinx/RTD",
             "autodoc tree). Regenerate with:", "",
             "```bash", "JAX_PLATFORMS=cpu python docs/generate_api.py",
             "```", ""]
    for group, modnames in MODULES.items():
        index += [f"## {group}", ""]
        for modname in modnames:
            page = modname.replace("production_stack_tpu.", "").replace(
                ".", "_") + ".md"
            try:
                content = render_module(modname)
            except ImportError as e:
                # a module gated on an optional dependency this
                # environment lacks: keep its EXISTING page and keep
                # going (every other page must still regenerate). A
                # module with no page at all (typo'd MODULES entry,
                # never-rendered new module) still hard-fails — the
                # index must never link to a page that does not exist
                if os.path.exists(os.path.join(api_dir, page)):
                    print(f"skipped {modname} (missing optional "
                          f"dependency: {e}); existing page kept")
                    index += [f"- [`{modname}`]({page})"]
                    continue
                raise SystemExit(f"failed to render {modname}: {e}")
            except Exception as e:       # a page must never be silently
                raise SystemExit(        # stale or half-written
                    f"failed to render {modname}: {e}")
            with open(os.path.join(api_dir, page), "w") as f:
                f.write(content)
            mod = importlib.import_module(modname)
            first = (_doc(mod).splitlines() or [""])[0]
            index += [f"- [`{modname}`]({page}) — {first}"]
        index += [""]
    with open(os.path.join(api_dir, "README.md"), "w") as f:
        f.write("\n".join(index).rstrip() + "\n")
    total = sum(len(v) for v in MODULES.values())
    print(f"wrote {total} module pages + index to {api_dir}")


if __name__ == "__main__":
    main()
