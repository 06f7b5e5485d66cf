"""Runtime init/finalize — wires control plane, transports, p2p, collectives.

Re-design of the reference's staged bring-up (SURVEY.md §3.1):
ompi_mpi_init (ompi/runtime/ompi_mpi_init.c:302) →
ompi_mpi_instance_init_common (ompi/instance/instance.c:347): RTE/PMIx init,
framework opens, modex + fence, then COMM_WORLD construction. Here:

    Context(bootstrap):
      1. per-rank progress engine (≙ opal_progress init)
      2. open/select transport modules, publish addresses   (≙ btl add_procs)
      3. bootstrap.fence()                                   (≙ PMIx fence —
         the ONLY collective in startup, instance.c:529-596)
      4. p2p protocol engine                                 (≙ pml select)
      5. COMM_WORLD with the coll framework's per-comm table (≙ comm_init_mpi3)

A Context is one *rank*. Multi-process jobs have one per process (tpurun
environment contract); threaded single-host jobs create N in one process —
the reference's single-host test stance (SURVEY.md §4). The singleton path
(no launcher env) gives a size-1 world, like singleton MPI init.

Thread level: FUNNELED — exactly one thread per Context may call into
p2p/coll (the matching engine, transports, and selector are driven from that
thread's progress loop, unlocked). Multiple Contexts in one process (threaded
ranks) are fully independent.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, List, Optional

from .control import Bootstrap, from_environment
from .core.component import frameworks
from .core.output import output
from .core.progress import ProgressEngine, set_engine
from .core import var as _rtvar
from .p2p import selftrans, shm, tcp  # noqa: F401  (register transports)
from .p2p.pml import P2P
from .p2p.transport import TransportLayer

_rtvar.register(
    "runtime", "", "async_progress", False, type=bool, level=3,
    help="Start a per-rank progress thread at init (≙ the reference "
         "servicing opal_progress unconditionally): passive-target RMA "
         "and rendezvous service keep moving while the application "
         "thread computes. Off by default — but windows AUTO-START the "
         "thread (async_progress_auto), which is where unconditional "
         "progress is load-bearing.")
_rtvar.register(
    "runtime", "", "async_progress_auto", True, type=bool, level=3,
    help="Auto-start the progress thread when the first RMA window is "
         "created, so passive-target synchronization never stalls on a "
         "compute-busy target without opt-in (≙ opal_progress.c:216 "
         "being unconditional in the reference). Disable to force the "
         "strictly-funneled single-thread mode.")


class Context:
    def __init__(self, bootstrap: Optional[Bootstrap] = None) -> None:
        self.bootstrap = bootstrap if bootstrap is not None else from_environment()
        self.rank = self.bootstrap.rank
        # "world" = this job's ranks. A dynamically-spawned child job
        # (dpm.spawn) lives at [WORLD_BASE, WORLD_BASE+WORLD_SIZE) of the
        # grown global rank space: its COMM_WORLD covers only its own ranks
        # (MPI semantics — children get their own world, talking to parents
        # through the spawn intercommunicator), while transports address
        # the full global space.
        import os as _os
        wbase = int(_os.environ.get("OMPI_TPU_WORLD_BASE", "0"))
        wsize = int(_os.environ.get("OMPI_TPU_WORLD_SIZE",
                                    str(self.bootstrap.size)))
        self.world_ranks = list(range(wbase, wbase + wsize))
        self.world_cid = (0 if wbase == 0
                          else (1 << 43) | int(_os.environ.get(
                              "OMPI_TPU_SPAWN_GROUP", "0")))
        self.size = wsize
        # CPU binding (≙ PRRTE applying the hwloc cpuset before app start):
        # the launcher computes per-rank cpusets (--bind-to) and passes
        # them down; a rank binds itself first thing so every thread it
        # spawns (progress, io worker) inherits the set
        from .core import hwtopo
        self.bound_cpus = hwtopo.apply_env_binding()
        self.engine = ProgressEngine()
        from .core import var as _var0
        self._async_progress = bool(_var0.get("runtime_async_progress",
                                              False))
        # the guard is ALWAYS an RLock: the progress thread may start
        # lazily (first window → ensure_async_progress), and transports
        # capture the guard at init — measured cost on the p2p latency
        # class is recorded in BASELINE.md (sub-µs per entry point)
        self.engine.guard = threading.RLock()
        self._prog_thread = None
        self.am_table: dict = {}
        mods = []
        for pri, comp, mod in frameworks.framework("transport").select_all(self):
            mod.dispatch = self.am_table
            mod.init_job(self.bootstrap)
            mods.append(mod)
        if not mods:
            raise RuntimeError("no transport components available")
        self.bootstrap.fence()
        self.layer = TransportLayer(mods)
        self.layer.guard = self.engine.guard
        self._install_idle_hook(mods)
        from .spc import Counters
        self.spc = Counters()
        from .p2p.pmlx import maybe_native
        self.p2p = maybe_native(self.bootstrap, self.layer, self.engine,
                                spc=self.spc) \
            or P2P(self.bootstrap, self.layer, self.engine, spc=self.spc)
        self._comm_world = None
        self.finalized = False
        # blocking waits on this thread must pump THIS context's engine even
        # when the user constructs Context directly instead of runtime.init()
        from .core.progress import adopt_engine
        adopt_engine(self.engine)
        from . import memchecker         # registers memchecker_enabled
        from .core import var as _var
        if _var.get("memchecker_enabled", False):
            memchecker.install(self)    # --mca memchecker_enabled 1
        from . import health
        if health.enabled:
            # live health plane: watchdog progress callback + daemon
            # thread + optional HTTP endpoint (one attribute read when
            # the plane is off — no import cost either, health is
            # already loaded via p2p.request)
            health.install(self)
        from . import hook
        hook.fire("init_bottom", self)   # ≙ mca/hook mpi_init hooks
        _ctx_opened()                    # interlib: a runtime is now live
        if self._async_progress:
            self.ensure_async_progress()

    def ensure_async_progress(self) -> None:
        """Start the per-rank progress thread (idempotent). Called at init
        when runtime_async_progress is set, and automatically by the first
        RMA window (unless async_progress_auto is off) — the path where
        the reference's unconditional opal_progress servicing
        (opal_progress.c:216) is load-bearing: a lock/flush against a
        compute-busy target must not stall until the target polls."""
        if self._prog_thread is not None or self.finalized:
            return
        import time as _time

        self._async_progress = True

        def _pump() -> None:
            while not self.finalized:
                n = self.engine.progress()
                # back off when idle: on oversubscribed hosts a hot
                # spinner starves the app thread it exists to serve
                _time.sleep(0 if n else 0.001)

        self._prog_thread = threading.Thread(
            target=_pump, name=f"ompi-tpu-prog-{self.rank}", daemon=True)
        self._prog_thread.start()

    def _install_idle_hook(self, mods) -> None:
        """Wire the engine's blocking idle hook: block on the shm doorbell
        when going idle, but cap the block to ~100µs while doorbell-less
        transports (tcp) have live connections — their frames arrive in
        kernel buffers no semaphore announces."""
        waiter = next((t.idle_wait for t in mods if hasattr(t, "idle_wait")),
                      None)
        if waiter is None:
            return
        others = [t.has_activity for t in mods if hasattr(t, "has_activity")]

        def hook(timeout: float) -> None:
            if any(act() for act in others):
                timeout = min(timeout, 0.0001)
            waiter(timeout)

        self.engine.idle_wait = hook

    @property
    def comm_world(self):
        """COMM_WORLD, built lazily (imports the comm layer on first use)."""
        if self._comm_world is None:
            from .comm import Communicator
            self._comm_world = Communicator._world(self)
        return self._comm_world

    def finalize(self) -> None:
        if self.finalized:
            return
        self.finalized = True
        _ctx_closed()
        from . import health
        health.uninstall(self)   # no-op when the plane was never installed
        if self._prog_thread is not None:
            # pump loop exits on the finalized flag; rejoin so the rest of
            # finalize (drain, fence) runs back under the FUNNELED contract
            self._prog_thread.join(timeout=5)
            self._prog_thread = None
        from .core import var as _var
        self.spc._v["progress_polls"] = self.engine.polls
        self.spc._v["time_in_wait"] = self.engine.time_waiting
        if _var.get("spc_dump_enabled", False):
            self.spc.dump(self.rank)
        if getattr(self, "_monitor", None) is not None:
            from . import monitoring
            monitoring.finalize_dump(self)
        from . import hook
        hook.fire("finalize_top", self)  # ≙ mca/hook mpi_finalize hooks
        # Drain transports before fencing: frames parked when a ring/socket
        # was full (e.g. shm's _pending queue) must reach the wire, or a
        # peer still blocked in recv never completes. The reference runs
        # opal_progress inside every blocking point for exactly this
        # (opal/runtime/opal_progress.c:216); finalize is a blocking point.
        # Frames destined to failed ranks are not waited on (their ring
        # never drains), and an idle spin yields so a 1-core host can run
        # the peers whose progress we're waiting for.
        import time as _time
        dead = frozenset(getattr(self, "failed", ()))
        deadline = _time.monotonic() + 10.0
        while any(t.pending_count(dead) for t in self.layer.transports):
            if self.engine.progress() == 0:
                _time.sleep(0.0005)
            if _time.monotonic() > deadline:
                output.verbose(
                    1, "runtime",
                    "finalize: transports still have pending frames after "
                    "10s; proceeding to fence anyway")
                break
        try:
            self.bootstrap.fence()
        except Exception as exc:
            output.verbose(1, "runtime", f"finalize fence failed: {exc}")
        if hasattr(self.p2p, "finalize"):
            self.p2p.finalize()         # native engine teardown before rings
        for t in self.layer.transports:
            t.finalize()
        self.bootstrap.finalize()

    def abort(self, code: int = 1, msg: str = "") -> None:
        """MPI_Abort semantics: notify the control plane (so the launcher
        and fence/get-blocked peers learn), then — when this process hosts
        exactly this rank — terminate it (MPI_Abort does not return,
        ompi/mpi/c/abort.c). Threaded in-process ranks (run_ranks) only
        notify: killing the host process would take out peer ranks and the
        harness; their LocalBootstrap wakes peers instead.

        Exit-status clamp: POSIX statuses are 8-bit, and an abort must
        never look like success, so the reported status is
        ``(code & 0xFF) or 1`` — errorcode 0 and any multiple of 256 both
        surface as status 1. Launcher-side consumers comparing statuses to
        the original errorcode should compare mod 256 (0 ≙ 1)."""
        try:
            self.bootstrap.abort(code, msg)
        finally:
            if getattr(self.bootstrap, "process_scoped", False):
                import os as _os
                # exit statuses are 8-bit: clamp so an abort can never
                # report success (e.g. code 256 -> status 0)
                _os._exit((int(code) & 0xFF) or 1)

    # -- control-plane events (the canonical poll point) ---------------------

    def push_event(self, ev: dict) -> None:
        """Re-queue an event another consumer drained but doesn't own."""
        if not hasattr(self, "_event_backlog"):
            self._event_backlog = []
        self._event_backlog.append(ev)

    def poll_events(self) -> list:
        """Backlogged + freshly-arrived control-plane events. Consumers that
        drain events they don't own must push_event() them back."""
        out = getattr(self, "_event_backlog", [])
        self._event_backlog = []
        out.extend(self.bootstrap.poll_events())
        return out


_process_ctx: Optional[Context] = None


def init(bootstrap: Optional[Bootstrap] = None) -> Context:
    """Process-level init (≙ MPI_Init). Idempotent."""
    global _process_ctx
    if _process_ctx is None or _process_ctx.finalized:
        _process_ctx = Context(bootstrap)
        set_engine(_process_ctx.engine)
        # worker threads the user spawns must poll this engine too
        from .core.progress import set_process_engine
        set_process_engine(_process_ctx.engine)
    return _process_ctx


def finalize() -> None:
    global _process_ctx
    if _process_ctx is not None:
        _process_ctx.finalize()
        _process_ctx = None


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile and return its directory.  A ``JAX_COMPILATION_CACHE_DIR``
    from the environment (which jax reads itself) wins untouched;
    otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed
    path, since the path is part of the cache key."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    return d


_job_seq = 0


def run_ranks(n: int, fn: Callable[[Context], object],
              timeout: float = 60.0) -> List[object]:
    """Run ``fn(ctx)`` on n threaded ranks wired through a LocalBootstrap —
    the in-process analog of ``tpurun -np n`` used by the test suite
    (SURVEY.md §4: the reference tests multi-rank logic single-host)."""
    import os

    from .control.bootstrap import LocalBootstrap

    global _job_seq
    _job_seq += 1
    boots = LocalBootstrap.create_job(
        n, job_id=f"thr{os.getpid()}n{_job_seq}")
    results: List[object] = [None] * n
    errors: List[BaseException | None] = [None] * n

    def runner(r: int) -> None:
        ctx = None
        try:
            ctx = Context(boots[r])
            set_engine(ctx.engine)
            results[r] = fn(ctx)
        except BaseException as exc:  # noqa: BLE001 — surfaced to caller
            errors[r] = exc
            boots[r].abort(1, f"rank {r}: {exc!r}")
        finally:
            if ctx is not None:
                try:
                    ctx.finalize()
                except Exception:
                    pass
            set_engine(None)

    threads = [threading.Thread(target=runner, args=(r,), daemon=True)
               for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        if t.is_alive():
            raise TimeoutError("run_ranks: rank thread did not finish")
    for r, exc in enumerate(errors):
        if exc is not None:
            raise exc
    return results


# ---------------------------------------------------------------------------
# interlib: multi-runtime coordination (≙ ompi/interlib/interlib.c:1)
# ---------------------------------------------------------------------------
# The reference lets independently-written libraries in one process declare
# their use of the MPI runtime (via MPI_T init under the covers) so init/
# finalize and thread levels compose instead of colliding. The analog here:
# an embedding framework (a serving stack, another collective library)
# declares itself before using ompi_tpu, and can query who else is resident
# and whether a Context is live, instead of guessing from side effects.

_interlib: Dict[str, dict] = {}
_interlib_lock = threading.Lock()
_n_live_contexts = 0


def _ctx_opened() -> None:
    global _n_live_contexts
    with _interlib_lock:
        _n_live_contexts += 1


def _ctx_closed() -> None:
    global _n_live_contexts
    with _interlib_lock:
        _n_live_contexts = max(0, _n_live_contexts - 1)


def _live_contexts() -> int:
    with _interlib_lock:
        return _n_live_contexts

THREAD_SINGLE = 0
THREAD_FUNNELED = 1
THREAD_SERIALIZED = 2
THREAD_MULTIPLE = 3


def interlib_declare(name: str, version: str = "",
                     thread_level: int = THREAD_MULTIPLE) -> None:
    """Declare a co-resident runtime/library (≙ ompi_interlib_declare).
    Re-declaring the same name updates its record; the effective process
    thread level is the MINIMUM of every declaration (the most restrictive
    resident library wins, like MPI_Init_thread's provided level)."""
    with _interlib_lock:
        _interlib[str(name)] = {"version": str(version),
                                "thread_level": int(thread_level)}


def interlib_withdraw(name: str) -> bool:
    """Remove a declaration (library unloaded/finalized)."""
    with _interlib_lock:
        return _interlib.pop(str(name), None) is not None


def interlib_query() -> dict:
    """Who shares this process: declared libraries, the effective thread
    level, and whether any ompi_tpu runtime is currently live (init()'s
    singleton OR directly-constructed / run_ranks Contexts — the count is
    maintained by Context init/finalize)."""
    with _interlib_lock:
        libs = {k: dict(v) for k, v in _interlib.items()}
    levels = [v["thread_level"] for v in libs.values()]
    return {
        "libraries": libs,
        "thread_level": min(levels) if levels else THREAD_MULTIPLE,
        "runtime_active": _live_contexts() > 0,
    }
