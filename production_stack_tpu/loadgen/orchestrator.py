"""Scale-out orchestrator: N engines + router, measured end to end.

Launches N engine processes (real ``production_stack_tpu.engine.server``
serving ``debug-tiny`` on CPU, or the test fake engine) plus the real
router with a chosen routing policy, runs the SAME seeded workload at
each replica count, and emits the aggregate-tokens/s-vs-replicas curve
(``SCALEOUT_*.json``) — the stack's core DP scale-out claim (BASELINE
config 2), previously never measured.

Everything is public surface: subprocesses + HTTP. The orchestrator
never imports engine or router internals.
"""

import asyncio
import dataclasses
import os
import socket
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import aiohttp

from production_stack_tpu.loadgen.report import scaleout_record, write_json
from production_stack_tpu.loadgen.runner import run_workload, warmup_spec
from production_stack_tpu.loadgen.spec import WorkloadSpec
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# engine CLI geometry for CPU scale-out runs: small enough that warmup
# compiles in tens of seconds, big enough to hold a full "scaleout" /
# "mixed" preset session (their round-3 histories reach ~800 model
# tokens under debug-tiny's character-level tokenizer)
ENGINE_ARGS = ["--max-model-len", "1024", "--max-num-seqs", "8",
               "--prefill-chunk", "64", "--decode-window", "8",
               "--kv-len-buckets", "256,512,1024"]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class Proc:
    name: str
    popen: subprocess.Popen
    url: str
    log_path: str


def _spawn(name: str, cmd: List[str], url: str, log_dir: str,
           env: Optional[Dict[str, str]] = None) -> Proc:
    os.makedirs(log_dir, exist_ok=True)
    log_path = os.path.join(log_dir, f"{name}.log")
    log = open(log_path, "ab")
    popen = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                             cwd=REPO_ROOT,
                             env={**os.environ, **(env or {})})
    log.close()
    return Proc(name=name, popen=popen, url=url, log_path=log_path)


# real-engine children launched off the CPU: each claims every chip of
# the host at start-up, so a second one alive at the same time fails or
# hangs inside JAX with nothing to say why. launch_engine refuses it
# here instead. (Replicas on one host's chips need an engine pinned to
# a device in-process — ROADMAP R5.)
_chip_owners: List[subprocess.Popen] = []


def launch_engine(kind: str, port: int, *, log_dir: str,
                  platform: str = "cpu",
                  extra_args: Optional[List[str]] = None,
                  geometry: Optional[List[str]] = None,
                  env: Optional[Dict[str, str]] = None) -> Proc:
    """kind "fake" -> tests/fake_engine.py mock; anything else is a
    model name served by the real engine server at ``geometry``
    (default ENGINE_ARGS, the CPU drills' small one). ``platform``
    becomes the child's JAX_PLATFORMS ("" = inherit, i.e. the chip
    where there is one); the launcher itself never imports JAX. A chip
    belongs to one process: a second chip-owning child while one is
    alive raises."""
    url = f"http://127.0.0.1:{port}"
    if kind == "fake":
        # defaults pace the mock like a tiny real engine; extra_args
        # can override (the overhead A/B pins a zero-think engine so
        # the measurement is the router, not the pacing)
        cmd = [sys.executable, "-m", "tests.fake_engine",
               "--port", str(port), "--host", "127.0.0.1",
               "--model", "fake-model", "--num-tokens", "16",
               "--tokens-per-s", "200", *(extra_args or [])]
        return _spawn(f"engine-fake-{port}", cmd, url, log_dir)
    cmd = [sys.executable, "-m", "production_stack_tpu.engine.server",
           "--model", kind, "--host", "127.0.0.1", "--port", str(port),
           *(ENGINE_ARGS if geometry is None else geometry),
           *(extra_args or [])]
    env = dict(env or {})
    if platform:
        env["JAX_PLATFORMS"] = platform
    on_chip = platform != "cpu"
    if on_chip:
        _chip_owners[:] = [p for p in _chip_owners if p.poll() is None]
        if _chip_owners:
            raise RuntimeError(
                f"engine child pid {_chip_owners[0].pid} already owns "
                f"this host's chip(s): a chip belongs to one process at "
                f"a time, so a second chip-owning engine "
                f"(JAX_PLATFORMS={platform or 'inherited'!r}) would "
                f"fail or hang. Run replicas with platform='cpu', or "
                f"one engine across chips with --tensor-parallel-size")
    proc = _spawn(f"engine-{kind}-{port}", cmd, url, log_dir, env=env)
    if on_chip:
        _chip_owners.append(proc.popen)
    return proc


def launch_cache_server(port: int, *, log_dir: str,
                        capacity_gb: float = 1.0) -> Proc:
    """Shared TPKV cache server (python backend — the rigs measure the
    serving stack, not the C++ store). Proc.url is the tpukv:// URL
    engines take as their remote tier."""
    cmd = [sys.executable, "-m", "production_stack_tpu.kvcache.server",
           "--host", "127.0.0.1", "--port", str(port),
           "--capacity-gb", str(capacity_gb), "--backend", "python"]
    return _spawn(f"cache-server-{port}", cmd,
                  f"tpukv://127.0.0.1:{port}", log_dir)


async def wait_cache_ready(url: str, timeout_s: float = 30.0) -> None:
    """Poll a TPKV server with PING until it answers."""
    from production_stack_tpu.kvcache.store import RemoteStore
    client = RemoteStore(url, connect_timeout=0.5, io_timeout=2.0,
                         breaker_threshold=1 << 30)
    deadline = time.monotonic() + timeout_s
    try:
        while time.monotonic() < deadline:
            if await asyncio.to_thread(client.ping):
                return
            await asyncio.sleep(0.3)
    finally:
        client.close()
    raise TimeoutError(f"cache server {url} not answering PING "
                       f"after {timeout_s:.0f}s")


def launch_router(backend_urls: List[str], model: str, port: int, *,
                  routing: str = "session", log_dir: str,
                  snapshot_ttl: Optional[float] = None,
                  extra_args: Optional[List[str]] = None) -> Proc:
    cmd = [sys.executable, "-m", "production_stack_tpu.router.app",
           "--host", "127.0.0.1", "--port", str(port),
           "--service-discovery", "static",
           "--static-backends", ",".join(backend_urls),
           "--static-models", ",".join([model] * len(backend_urls)),
           "--routing-logic", routing,
           "--engine-stats-interval", "5"]
    if snapshot_ttl is not None:
        cmd += ["--request-stats-snapshot-ttl", str(snapshot_ttl)]
    cmd += extra_args or []
    return _spawn(f"router-{port}", cmd, f"http://127.0.0.1:{port}",
                  log_dir)


def launch_obsplane(router_urls: List[str], engine_urls: List[str],
                    port: int, *, log_dir: str,
                    incident_dir: str,
                    extra_args: Optional[List[str]] = None) -> Proc:
    """The fleet observability aggregator (obsplane/app.py): scrapes
    every router and engine, stitches traces online, and captures
    alert-triggered incident bundles into ``incident_dir``."""
    cmd = [sys.executable, "-m", "production_stack_tpu.obsplane",
           "--host", "127.0.0.1", "--port", str(port),
           "--routers", ",".join(router_urls),
           "--engines", ",".join(engine_urls),
           "--incident-dir", incident_dir,
           *(extra_args or [])]
    return _spawn(f"obsplane-{port}", cmd, f"http://127.0.0.1:{port}",
                  log_dir)


def launch_kvplane(replica_urls: List[str], port: int, *,
                   log_dir: str, router_url: Optional[str] = None,
                   extra_args: Optional[List[str]] = None) -> Proc:
    """The fleet KV memory planner (kvplane/app.py): polls every
    replica's /load kv_pool census and erases fragmented-admission
    failures by migrating KV replica-to-replica."""
    cmd = [sys.executable, "-m", "production_stack_tpu.kvplane",
           "--host", "127.0.0.1", "--port", str(port),
           "--replicas", ",".join(replica_urls)]
    if router_url:
        cmd += ["--router", router_url]
    cmd += extra_args or []
    return _spawn(f"kvplane-{port}", cmd, f"http://127.0.0.1:{port}",
                  log_dir)


async def wait_healthy(url: str, timeout_s: float,
                       require_endpoints: int = 0) -> None:
    """Poll /health until 200 (and, for the router, until it can route
    to ``require_endpoints`` backends)."""
    deadline = time.monotonic() + timeout_s
    last_err = "never polled"
    async with aiohttp.ClientSession() as session:
        while time.monotonic() < deadline:
            try:
                async with session.get(
                        f"{url}/health",
                        timeout=aiohttp.ClientTimeout(total=5)) as r:
                    if r.status == 200:
                        if require_endpoints == 0:
                            return
                        body = await r.json()
                        if body.get("endpoints", 0) >= require_endpoints:
                            return
                        last_err = f"endpoints={body.get('endpoints')}"
                    else:
                        last_err = f"HTTP {r.status}"
            except (aiohttp.ClientError, ConnectionError, OSError,
                    asyncio.TimeoutError) as e:
                last_err = f"{type(e).__name__}"
            await asyncio.sleep(0.5)
    raise TimeoutError(f"{url}/health not ready after {timeout_s:.0f}s "
                       f"(last: {last_err})")


def _stop(procs: List[Proc]) -> None:
    for p in procs:
        if p.popen.poll() is None:
            p.popen.terminate()
    deadline = time.monotonic() + 10
    for p in procs:
        try:
            p.popen.wait(timeout=max(0.1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.popen.kill()
            p.popen.wait(timeout=5)


class LocalStack:
    """N engines + 1 router on localhost; async context manager."""

    def __init__(self, replicas: int, engine: str = "debug-tiny", *,
                 routing: str = "session", log_dir: str = "loadgen-logs",
                 platform: str = "cpu", startup_timeout_s: float = 420.0,
                 engine_args: Optional[List[str]] = None):
        self.replicas = replicas
        self.engine = engine
        self.routing = routing
        self.log_dir = log_dir
        self.platform = platform
        self.startup_timeout_s = startup_timeout_s
        self.engine_args = engine_args
        self.procs: List[Proc] = []
        self.engine_urls: List[str] = []
        self.url: Optional[str] = None

    async def __aenter__(self) -> "LocalStack":
        try:
            engines = [launch_engine(self.engine, free_port(),
                                     log_dir=self.log_dir,
                                     platform=self.platform,
                                     extra_args=self.engine_args)
                       for _ in range(self.replicas)]
            self.procs.extend(engines)
            self.engine_urls = [e.url for e in engines]
            # engines warm up concurrently (each compiles its own
            # executables); health gates on warmup completion
            await asyncio.gather(*[
                wait_healthy(e.url, self.startup_timeout_s)
                for e in engines])
            model = "fake-model" if self.engine == "fake" else self.engine
            router = launch_router([e.url for e in engines], model,
                                   free_port(), routing=self.routing,
                                   log_dir=self.log_dir)
            self.procs.append(router)
            await wait_healthy(router.url, 60.0,
                               require_endpoints=self.replicas)
            self.url = router.url
            return self
        except BaseException:
            for p in self.procs:
                if p.popen.poll() is not None:
                    logger.error("%s exited rc=%s; log: %s", p.name,
                                 p.popen.returncode, p.log_path)
            _stop(self.procs)
            raise

    async def __aexit__(self, *exc) -> None:
        _stop(self.procs)


async def run_scaleout(spec: WorkloadSpec, *,
                       replicas: List[int],
                       engine: str = "debug-tiny",
                       routing: str = "session",
                       duration_s: float = 60.0,
                       users_per_replica: Optional[int] = None,
                       platform: str = "cpu",
                       log_dir: str = "loadgen-logs",
                       startup_timeout_s: float = 420.0,
                       checkpoint_interval_s: Optional[float] = None,
                       output: Optional[str] = None) -> Dict:
    """Measure the same workload at each replica count; write and
    return the SCALEOUT record.

    The offered load scales with N (closed loop: users_per_replica × N
    concurrent users) so each point probes capacity, and the seeded
    session plans are identical across points — N is the only variable.
    """
    if users_per_replica is None:
        users_per_replica = spec.arrival.users
    points: List[Dict] = []
    for n in replicas:
        logger.info("scale-out point: %d replica(s) of %s via %s routing",
                    n, engine, routing)
        stack_log = os.path.join(log_dir, f"n{n}")
        async with LocalStack(n, engine, routing=routing,
                              log_dir=stack_log, platform=platform,
                              startup_timeout_s=startup_timeout_s) as stack:
            point_spec = WorkloadSpec.from_dict(dataclasses.asdict(spec))
            point_spec.arrival.users = users_per_replica * n
            if engine != "fake":
                point_spec.model = engine
            else:
                point_spec.model = "fake-model"
            # warm each engine DIRECTLY before the measured window:
            # consistent-hash session routing gives no guarantee that
            # router-side warmup traffic reaches every replica, and a
            # cold replica pays its first-request XLA compiles inside
            # the point it is supposed to be measured at
            for e_url in stack.engine_urls:
                warm = await run_workload(
                    warmup_spec(point_spec), e_url, max_sessions=2,
                    checkpoint_interval_s=1e9)
                if warm.summary["errors"]:
                    logger.warning(
                        "warmup against %s: %d/%d failed (first: %s) — "
                        "point N=%d may include compile time", e_url,
                        warm.summary["errors"], warm.summary["launched"],
                        (warm.summary["error_samples"] or ["?"])[0], n)
            result = await run_workload(
                point_spec, stack.url, duration_s=duration_s,
                checkpoint_interval_s=checkpoint_interval_s
                or max(15.0, duration_s / 4))
            agg = result.summary
            points.append({
                "replicas": n,
                "users": point_spec.arrival.users,
                "output_tokens_per_s": agg["output_tokens_per_s"],
                "input_tokens_per_s": agg["input_tokens_per_s"],
                "processed_qps": agg["processed_qps"],
                "errors": agg["errors"],
                "invariant_violations": result.violations,
                "ttft_s": agg["ttft_s"],
                "summary": agg,
            })
            logger.info("N=%d: %.2f out tok/s (%d finished, %d errors)",
                        n, agg["output_tokens_per_s"], agg["finished"],
                        agg["errors"])
            if agg["errors"]:
                logger.warning(
                    "N=%d point had %d errors — the curve is suspect. "
                    "First error: %s", n, agg["errors"],
                    (agg["error_samples"] or ["?"])[0])
    record = scaleout_record(engine=engine, routing=routing,
                             workload=spec.name, points=points,
                             platform=platform,
                             notes=f"duration {duration_s:.0f}s/point, "
                                   f"{users_per_replica} users per "
                                   f"replica, seed {spec.seed}")
    if output:
        write_json(output, record)
        logger.info("wrote %s", output)
    return record
