"""Test configuration: an 8-device virtual CPU, one compile cache a run,
and CPU executables built without the optimisations nobody reads.

All tests run hardware-free: JAX is pinned to the CPU platform with 8
virtual devices so sharding/collective code paths (tp/dp meshes) are
exercised as they would be on a TPU slice.

The rule the suite keeps (docs/testing.md): a test pays for the compile
of the program it asserts on, once a run, and for running it. So

- JAX's persistent compilation cache is placed for the run: a new, empty
  directory under the system's temporary directory (never the
  checkout's ``.jax_cache/``, which is the chip's), made by the process
  that starts the session and handed to xdist's workers and to the
  engine processes tests start through ``JAX_COMPILATION_CACHE_DIR``,
  which ``utils.compile_cache_dir`` honours in every entry point. It is
  removed when the session ends. Where the environment forbids bytecode
  beside the sources (``PYTHONDONTWRITEBYTECODE``), Python's own
  compiles go the same way: ``PYTHONPYCACHEPREFIX`` in that directory.
- the CPU back end compiles with ``jax_disable_most_optimizations``:
  tier-1 asserts what a program computes, never how fast a CPU runs it.
  tests/test_chip_compile.py reads the TPU compiler's OPTIMISED HLO and
  switches it back for its own compiles.

Must run before jax is imported anywhere in the test process.
"""

import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

os.environ["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "1"

# An xdist worker inherits the directory its controller made.
_RUN_DIR = None
if "PYTEST_XDIST_WORKER" not in os.environ:
    _RUN_DIR = tempfile.mkdtemp(prefix="pstpu-tier1-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_RUN_DIR, "jax")
    os.mkdir(os.environ["JAX_COMPILATION_CACHE_DIR"])
    # keep the half-second compiles too, whatever their size
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.2"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    if sys.dont_write_bytecode:
        # bytecode is kept out of the source trees here, so every worker
        # and every process a test starts would compile jax's sources
        # again (5 s an engine): the run keeps it beside its executables
        os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
        os.environ["PYTHONPYCACHEPREFIX"] = os.path.join(_RUN_DIR, "pyc")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _write_cache_entries_whole():
    """JAX writes an entry of the persistent cache in place
    (``LRUCache.put``: ``write_bytes``) and ``get`` reads whatever is
    there, so a worker that looks a key up while another writes it
    deserialises half an executable: a segmentation fault in
    ``compilation_cache.get_executable_and_time`` took a worker down in
    a whole run (PR 48; six workers build the same engines at the same
    time). Written beside and renamed, an entry is there whole or not at
    all. (A process a test starts still writes in place.)"""
    from jax._src import lru_cache
    in_place = lru_cache.LRUCache.put

    def put(self, key, val):
        if not key or self.eviction_enabled:
            return in_place(self, key, val)
        path = self.path / f"{key}{lru_cache._CACHE_SUFFIX}"
        if not path.exists():
            beside = self.path / f"{key}.{os.getpid()}.tmp"
            beside.write_bytes(val)
            os.replace(beside, path)
    lru_cache.LRUCache.put = put


_write_cache_entries_whole()


def pytest_unconfigure(config):
    if _RUN_DIR is not None:
        shutil.rmtree(_RUN_DIR, ignore_errors=True)
