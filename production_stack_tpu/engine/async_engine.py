"""AsyncLLMEngine: asyncio facade over the synchronous engine loop.

The engine loop runs on one dedicated thread (JAX dispatch is blocking);
results cross into the event loop via ``loop.call_soon_threadsafe`` onto
per-request asyncio queues. When idle the loop parks on a condition
variable so an idle engine burns no CPU.
"""

import asyncio
import threading
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import AsyncIterator, Dict, List, Optional, Tuple

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine, StepOutput
from production_stack_tpu.engine.scheduler import SamplingOptions
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)

_SENTINEL: Tuple = ()

# the loop timeline's lag probe (efficiency.LoopAccounting.sample): one
# task on the event loop sleeps this long and books what its wake-up
# overshot. 100 ms: ten entries a second of the ``loop`` ring
LAG_PROBE_S = 0.1


class AsyncLLMEngine:
    def __init__(self, cfg: EngineConfig, params=None, mesh=None):
        self.engine = LLMEngine(cfg, params=params, mesh=mesh)
        self._queues: Dict[str, asyncio.Queue] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._wake = threading.Condition()
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._lag_probe: Optional[asyncio.Task] = None
        # dedicated pool for calls that wait on the ENGINE LOCK
        # (add_request/abort): during a multi-second lazy compile the
        # lock is held and each waiting call pins a thread — on the
        # loop's SHARED default executor a burst would exhaust the pool
        # and stall unrelated offloaded work (DNS, embeddings). The
        # waits serialize on the lock anyway, so a few threads suffice.
        self._lock_pool = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="engine-lock")

    # ------------------------------------------------------------------

    def start(self, loop: Optional[asyncio.AbstractEventLoop] = None,
              warmup: bool = True) -> None:
        self._loop = loop or asyncio.get_event_loop()
        if self._lock_pool._shutdown:    # restarted after stop()
            self._lock_pool = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="engine-lock")
        if warmup:
            self.engine.runner.warmup()
        self._running = True
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="engine-loop")
        self._thread.start()
        # on the loop's own thread, whichever thread calls start()
        self._loop.call_soon_threadsafe(self._start_lag_probe)

    def _start_lag_probe(self) -> None:
        if self._running and self._lag_probe is None:
            self._lag_probe = self._loop.create_task(self._probe_lag())

    async def _probe_lag(self) -> None:
        acct = self.engine.eff.loop
        acct.sample(None)
        while self._running:
            await asyncio.sleep(LAG_PROBE_S)
            acct.sample(LAG_PROBE_S)

    def stop(self) -> None:
        self._running = False
        probe, self._lag_probe = self._lag_probe, None
        if probe is not None and not self._loop.is_closed():
            self._loop.call_soon_threadsafe(probe.cancel)
        with self._wake:
            self._wake.notify_all()
        if self._thread:
            self._thread.join(timeout=10)
        self._lock_pool.shutdown(wait=False)

    def _run(self) -> None:
        # the step timeline (engine/efficiency.py): what this loop
        # spends between one step()'s return and the next one's lock
        # is booked as between_steps, the wait for work as no_work
        phase = self.engine.eff.phase
        while self._running:
            if not self.engine.has_work:
                with phase("no_work"), self._wake:
                    if not self.engine.has_work and self._running:
                        self._wake.wait(timeout=0.2)
                continue
            try:
                outputs = self.engine.step()
            except Exception:
                logger.exception("engine step failed")
                continue
            if outputs and self._loop is not None:
                self._loop.call_soon_threadsafe(self._dispatch, outputs)

    def _dispatch(self, outputs: List[StepOutput]) -> None:
        # one stamp per batch of outputs, not per token: when a
        # request's first and last tokens reach its queue (the
        # first_token_emit / emit_lag trace events). The loop timeline
        # books the whole hand-over (totals.loop.dispatch_s)
        with self.engine.eff.loop.dispatching() as span:
            now = span.t0
            for out in outputs:
                if out.waits is not None:
                    if out.waits.first_emit is None:
                        out.waits.first_emit = now
                    if out.finished:
                        out.waits.last_emit = now
                q = self._queues.get(out.seq_id)
                if q is not None:
                    q.put_nowait(out)
                    if out.finished:
                        self._queues.pop(out.seq_id, None)

    # ------------------------------------------------------------------

    async def submit(self, prompt_tokens: List[int],
                     options: SamplingOptions,
                     seq_id: Optional[str] = None,
                     model: Optional[str] = None,
                     deadline: Optional[float] = None
                     ) -> Tuple[str, asyncio.Queue]:
        # add_request takes the ENGINE LOCK (engine.py), which the
        # engine thread holds across whole steps — including lazy XLA
        # compiles of new executable variants (seconds each). Taking
        # that lock here would block the EVENT LOOP: under a burst of
        # first-time feature combinations the server stops accepting
        # connections entirely (observed as connect-refused storms in
        # the r5 mixed-traffic soak). The executor thread absorbs the
        # wait; it also keeps the connector's tier prefetch IO off the
        # loop, as engine.add_request's contract expects.
        #
        # The seq_id is generated HERE so the result queue exists
        # before the engine can emit: once add_request returns on the
        # executor thread, the engine thread may prefill and dispatch
        # within its next iterations — registering the queue after the
        # await would race those first outputs.
        seq_id = seq_id or f"seq-{uuid.uuid4().hex[:12]}"
        if seq_id in self._queues:
            # silently replacing the live stream's queue would orphan
            # it (and the error-path pop below would then tear down the
            # WRONG stream's registration)
            raise ValueError(f"seq_id {seq_id!r} already has a live stream")
        q: asyncio.Queue = asyncio.Queue()
        self._queues[seq_id] = q
        loop = asyncio.get_running_loop()
        # submit directly (not run_in_executor) so the CONCURRENT
        # future stays reachable: on task cancellation asyncio cancels
        # the wrapper even though the executor call keeps running, so
        # only the concurrent future's state says whether add_request
        # actually completed.
        try:
            cfut = self._lock_pool.submit(
                lambda: self.engine.add_request(
                    prompt_tokens, options, seq_id=seq_id, model=model,
                    deadline=deadline))
        except RuntimeError:
            # pool already shut down (request raced stop()): the
            # request never entered the engine, but the registration
            # above must not outlive this admission attempt
            self._queues.pop(seq_id, None)
            raise
        try:
            await asyncio.wrap_future(cfut, loop=loop)
        except asyncio.CancelledError:
            # the executor call cannot be interrupted: add_request may
            # still COMPLETE after this cancellation (client vanished
            # while we waited on the engine lock). Abort the sequence
            # once the call settles, else the orphan decodes to its
            # token budget on a slot nobody is reading.
            self._queues.pop(seq_id, None)

            def _cleanup(f):
                if f.cancelled() or f.exception() is not None:
                    return          # request never entered the engine
                # runs on the pool worker that finished add_request (or
                # the loop thread if it settled before registration)
                try:
                    self._lock_pool.submit(self.engine.abort, seq_id)
                except RuntimeError:
                    # stop() shut the pool down while add_request was
                    # settling: abort inline rather than lose it (the
                    # callback machinery would swallow the RuntimeError
                    # and the admitted orphan would keep its slot)
                    try:
                        self.engine.abort(seq_id)
                    except Exception as e:
                        logger.warning("inline abort of %s failed: %s",
                                       seq_id, e)
            cfut.add_done_callback(_cleanup)
            raise
        except Exception:
            self._queues.pop(seq_id, None)
            raise
        with self._wake:
            self._wake.notify_all()
        return seq_id, q

    def abort(self, seq_id: str) -> None:
        """Abort a live request: the result-queue registration is freed
        SYNCHRONOUSLY (a shed/deadline abort of a still-WAITING sequence
        must not leave its queue lingering until the engine loop next
        notices), while the engine-side abort — which waits on the
        engine lock — is dispatched to an executor thread and not
        awaited. Cleanup paths may run under GeneratorExit where
        awaiting is illegal; abort is idempotent and slot-guarded, so
        ordering vs later admissions is safe."""
        if seq_id not in self._queues:
            return
        self._queues.pop(seq_id, None)
        try:
            f = self._lock_pool.submit(self.engine.abort, seq_id)
        except RuntimeError:
            # stop() already shut the pool down (server shutdown with
            # live streams): abort inline rather than lose it — the
            # engine thread is stopping, so the brief lock wait here
            # cannot stall a running loop.
            try:
                self.engine.abort(seq_id)
            except Exception as e:
                logger.warning("inline abort of %s failed: %s",
                               seq_id, e)
        else:
            f.add_done_callback(
                lambda f: f.exception() and logger.warning(
                    "async abort of %s failed: %s", seq_id,
                    f.exception()))

    async def stream(self, prompt_tokens: List[int],
                     options: SamplingOptions,
                     model: Optional[str] = None,
                     deadline: Optional[float] = None
                     ) -> AsyncIterator[StepOutput]:
        seq_id, q = await self.submit(prompt_tokens, options, model=model,
                                      deadline=deadline)
        try:
            while True:
                out = await q.get()
                yield out
                if out.finished:
                    return
        finally:
            # client disconnected mid-stream (or the consumer saw a
            # terminal output, making this a no-op): free the slot
            self.abort(seq_id)

    @property
    def tokenizer(self):
        return self.engine.tokenizer

    @property
    def model_name(self) -> str:
        return self.engine.model_cfg.name
