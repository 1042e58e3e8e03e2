"""The program's own clocks and counters: when each dispatched program
finished on the device, what the seam between waves cost, and how far
delivery ran behind."""

from __future__ import annotations

import logging
import queue as _queue
import threading
import weakref
from typing import Any, Dict, List, Optional, Tuple

from seldon_core_tpu.utils import jitwatch as _jitwatch

logger = logging.getLogger(__package__)


class _DeviceClock:
    """When each dispatched program of the wave loop FINISHED, stamped by
    one watcher thread an engine, and from that what the device did
    between them.

    The engine thread hands over ``(enq, out, transitions)`` a
    dispatch (:meth:`watch`: one tuple, one ``put``): ``enq`` is the
    seam's clock as the dispatch returned, ``out`` the smallest output
    of the program that is not donated onward, ``transitions`` the
    ``(where, t)`` at which the engine thread changed phase since the
    dispatch before.  The watcher blocks on each ``out`` in order (the
    GIL released) and reads the same clock as the block returns:
    ``done``.  The device runs one queue in order, so program *i*
    started at ``max(enq[i], done[i-1])``, ran ``done[i]`` minus that,
    and **the device sat idle before it for ``max(0, enq[i] -
    done[i-1])``** — laid over the transitions, that idle is booked to
    where the engine thread was (``by``).  ``busy + idle`` is the clock
    from the first enqueue to the last completion, exactly.

    A program with no output to wait on (None: its outputs are donated
    onward), or whose array was deleted under the watcher, has no stamp
    of its own: the next program's bounds it (the two count as one busy
    block, and no idle is booked between them).

    What it under-reads: ``done`` is late by the watcher's wake-up — a
    thread switch, and the wait for the GIL when the engine thread is
    in Python just then — so an idle interval is short by that much;
    and time between two programs' own operations, or under an eager
    operation between two dispatches, is not idle here.

    Every sum is the watcher's; ``totals`` is ONE tuple, replaced whole,
    so any thread reads a consistent four.  The thread starts with the
    first dispatch and ends on a sentinel: :meth:`stop` (``close()``),
    the seam's finalizer, or the process's ``atexit`` hook — it is never
    inside jax when the interpreter goes."""

    WHERE = ("no_work", "between", "admit", "prefill.pack", "prefill.call",
             "prefill.tail", "launch.plan", "launch.call", "launch.post",
             "wait", "harvest", "record")

    _live: "weakref.WeakSet[_DeviceClock]" = weakref.WeakSet()
    _hooked = False

    def __init__(self, clock):
        self._clock = clock
        self._queue: _queue.SimpleQueue = _queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        self._busy = 0.0
        self._idle = 0.0
        self._programs = 0
        self._by: Dict[str, float] = dict.fromkeys(self.WHERE, 0.0)
        self.totals: Tuple[float, float, int, Dict[str, float]] = (
            0.0, 0.0, 0, self._by)
        # the newest completion stamped; the start of a busy block no
        # stamp has closed yet and the programs in it; the engine
        # thread's phase as of the last transition handed over
        self._last_done: Optional[float] = None
        self._open: Optional[float] = None
        self._pending = 0
        self._where = "no_work"
        self.cpu_s = 0.0  # the watcher's own CPU seconds, at its exit

    # ---- the engine thread's side --------------------------------------

    def watch(self, enq: float, out: Any, transitions: list) -> None:
        if self._thread is None:
            if self._stopped:
                return
            self._start()
        self._queue.put((enq, out, transitions))

    def _start(self) -> None:
        cls = _DeviceClock
        if not cls._hooked:
            import atexit

            # registered after jax's own hooks, so run before them
            atexit.register(cls._stop_all)
            cls._hooked = True
        cls._live.add(self)
        self._thread = threading.Thread(
            target=self._run, name="seldon-device-clock", daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        """End the watcher (idempotent): what is queued is settled
        first, and nothing dispatched afterwards is watched."""
        self._stopped = True
        thread = self._thread
        if thread is not None and thread.is_alive():
            self._queue.put(None)
            if thread is not threading.current_thread():
                thread.join(timeout)

    @classmethod
    def _stop_all(cls) -> None:
        for clock in list(cls._live):
            clock.stop(timeout=2.0)

    # ---- the watcher's side --------------------------------------------

    def _run(self) -> None:
        import time as _time

        get, clock = self._queue.get, self._clock
        try:
            while True:
                item = get()
                if item is None:
                    return
                enq, out, transitions = item
                done = None
                if out is not None:
                    try:
                        out.block_until_ready()
                        done = clock()
                    except Exception:  # noqa: BLE001 — deleted under us, or
                        pass           # the device failed: the next stamp bounds it
                del out, item
                try:
                    self.settle(enq, done, transitions)
                except Exception:  # noqa: BLE001 — never raises into serving
                    logger.exception("device clock: a stamp was not settled")
        finally:
            self.cpu_s = _time.thread_time()
            logger.info(
                "device clock: %d programs stamped, busy %.3f s, idle %.3f s; "
                "the watcher's own CPU %.3f s",
                self._programs, self._busy, self._idle, self.cpu_s)

    def settle(self, enq: float, done: Optional[float], transitions) -> None:
        """Program enqueued at ``enq``, finished by ``done`` (None: no
        stamp of its own), the engine thread's ``(where, t)`` since the
        enqueue before."""
        if self._open is None:
            last = self._last_done
            if last is None:
                self._open = enq
            elif enq > last:
                self._book(last, enq, transitions)
                self._idle += enq - last
                self._open = enq
            else:
                self._open = last
        if transitions:
            self._where = transitions[-1][0]
        self._pending += 1
        if done is not None:
            self._busy += done - self._open
            self._programs += self._pending
            self._pending = 0
            self._last_done = done
            self._open = None
        self.totals = (self._busy, self._idle, self._programs, self._by)

    def _book(self, a: float, b: float, transitions) -> None:
        """The idle interval ``[a, b]`` by where the engine thread was:
        split at every transition inside it."""
        by = dict(self._by)  # copied, so a published dict never changes
        where, since = self._where, a
        for name, t in transitions:
            if t >= b:
                break
            if t > since:
                by[where] = by.get(where, 0.0) + (t - since)
                since = t
            where = name
        by[where] = by.get(where, 0.0) + (b - since)
        self._by = by


class _WaveSeam:
    """The one seam every host phase and every device call of the wave
    loop passes through.  Always on: what it costs is in every run.

    * **Phases on the profiler's clock.**  ``begin_wave`` opens a
      ``jax.profiler.StepTraceAnnotation`` (``seldon.wave``, ``step_num``
      = the number of the wave this step launches) and ``enter`` a
      ``TraceAnnotation`` (``seldon.wave.<phase>``) that lasts until the
      next ``enter``, so the phases tile the step: ``admit``, ``launch``,
      then ``wait``, ``harvest``, ``record``.  In the serving loop the
      last three belong to the PREVIOUS wave, launched one step earlier
      and harvested under the chunk this step just enqueued; each
      carries its own ``wave=``.  ``prefill`` (one per
      ``_prefill_group`` call) nests inside whichever of them runs it.
      Two phases are tiled again by ``sub``: ``prefill`` by
      ``seldon.wave.prefill.{pack,call,tail}`` (the numpy tables and
      their puts; the jitted call; the eager tail that installs the
      decode state) and ``launch`` by ``seldon.wave.launch.{plan,call,
      post}`` (under the lock: retire, growth, tables; the argument puts
      and the chunk's dispatch; the screen, the async copies, the
      ``_Wave``).  They land on the engine thread's line of the host
      plane of the same ``.xplane.pb`` as the device's operations; with
      no profiler session open each is a flag test.
    * **The device's idle time, by where the engine thread was.**
      ``dispatched(out)`` marks the return of a dispatch of a program of
      the wave loop (its argument transfers, signature walk and enqueue
      are host work the device waits for), numbers it (``seq``) and
      hands its stamp, ``out`` and the phase transitions since the
      dispatch before to the :class:`_DeviceClock`, whose watcher thread
      stamps the program's completion: ``device_busy_s``,
      ``device_idle_s``, ``device_idle_by_s``.  ``no_work`` is the time
      from ``end_wave(False)`` (no stream admitted or queued) to the
      next ``begin_wave``: the callers' turn-around, not the host's.
    * **The host gap** (``host_gap_s``; blind since PR 29 wherever a
      chunk is enqueued ahead; see ``device_idle_s``).
      ``drained(upto)`` marks the return of a blocking readback
      of what dispatch ``upto`` produced.  The device runs one queue in
      order, so everything up to ``upto`` has run; the gap opens only
      if nothing was dispatched after it, i.e. nothing is in flight any
      more, and lasts to the next dispatch.  A wave that leaves no work
      behind closes the gap uncounted.  The serving loop enqueues a
      chunk before it reads the one before, so there the gap never
      opens while the device drains all the same.
    * **The engine thread's time, always.**  Every phase's wall time is
      booked where it ends, gap or no gap (``phase_walls``): one clock
      read a phase.  ``wait`` is the thread blocked in a readback — the
      device sets the pace; every other phase but ``between`` (waiting
      for a request) is the host's work, and once ``host_work_s``
      nears ``host_work_s + host_wait_s`` the host sets it.
    * **Compiles, where they happen.**  ``compile_context`` tells the
      process's backend-compile listener (``utils/jitwatch.py``) the
      open phase and the wave as a compile fires on the engine thread.
    * **The profile window.**  ``arm`` asks for ``seconds`` of
      ``jax.profiler`` trace under ``SELDON_TPU_PROFILE_DIR``;
      ``boundary`` (every wave boundary, on the engine thread) starts it,
      stops it at the first boundary after ``seconds`` and keeps an
      ``engine_stats()`` snapshot taken at each of the two instants.
    """

    PHASES = ("admit", "prefill", "launch", "wait", "harvest", "record",
              "between")
    # the phases ``sub`` tiles, and the part each opens with
    TILED = {"prefill": "pack", "launch": "plan"}
    # a transition's name -> the phase whose wall time it is
    _WALL_OF = dict(
        {w: w.partition(".")[0] for w in _DeviceClock.WHERE},
        no_work="between", **{p: p for p in PHASES})
    # transitions kept for one dispatch: a loop that turns without ever
    # dispatching must not grow the list
    MAX_TRANSITIONS = 4096

    def __init__(self, engine: "PagedEngine", profile_dir: Optional[str]):
        import time as _time

        self._engine = engine
        self._profiler = engine._jax.profiler
        self._clock = _time.perf_counter
        self._monotonic = _time.monotonic
        self.wave = 0
        # every phase's wall seconds so far, the phase now open and
        # where it began: ONE tuple, replaced whole where a phase ends,
        # so that another thread reads a consistent three (phase_walls)
        self._walls: Tuple[Dict[str, float], str, float] = (
            {p: 0.0 for p in self.PHASES}, "between", self._clock())
        # open annotations, outermost first: the step, its current
        # phase, a prefill group nested in that, the part of a tiled
        # phase — (transition name, annotation)
        self._open: List[Tuple[str, Any]] = []
        # whether the outermost of them is a step: a burst's last wave is
        # harvested with nothing left to launch, outside any step
        self._in_step = False
        self._phase = "no_work"
        self._gap_open = False
        self._gap_s = 0.0
        self._mark = 0.0
        # dispatches of wave-loop programs so far: a readback names the
        # one it waited for, and opens the gap only if it is the newest
        self.seq = 0
        # completions, and the (where, t) since the last dispatch
        self.device = _DeviceClock(self._clock)
        self._transitions: List[Tuple[str, float]] = []
        # the engine is dropped without close(): the watcher still ends
        weakref.finalize(self, self.device.stop, 0.0)
        # the process's compiles since this engine was built
        _jitwatch.watch_backend_compiles()
        self._compiles_base = _jitwatch.compile_totals()
        self._thread_ident: Optional[int] = None
        self._profile_dir = profile_dir
        self._profile_lock = threading.Lock()
        self._profile: Dict[str, Any] = {"state": "idle"}

    # ---- phases --------------------------------------------------------

    def _account(self, phase: str) -> None:
        """Book the wall time, and the open gap's time since the last
        mark, to the phase that ends here, and move on to ``phase``."""
        now = self._clock()
        walls, ending, since = self._walls
        walls = dict(walls)
        walls[ending] += now - since
        self._walls = (walls, self._WALL_OF.get(phase, phase), now)
        if self._gap_open:
            self._gap_s += now - self._mark
            self._mark = now
        self._phase = phase
        if len(self._transitions) < self.MAX_TRANSITIONS:
            self._transitions.append((phase, now))

    def _push(self, phase: str, annotation: Any) -> None:
        """Open ``annotation``; a tiled phase opens with its first part
        inside it, and the thread moves on to that."""
        annotation.__enter__()
        self._open.append((phase, annotation))
        part = self.TILED.get(phase)
        if part is not None:
            self.sub(part)
        else:
            self._account(phase)

    def _close(self, keep: int) -> None:
        """Close the open annotations down to the outermost ``keep``."""
        while len(self._open) > keep:
            self._open.pop()[1].__exit__(None, None, None)

    def _pop(self, keep: int = 0, then: str = "between") -> None:
        """Close down to ``keep``; the thread is back in the innermost
        of those, or in ``then``."""
        self._close(keep)
        self._account(self._open[-1][0] if self._open else then)

    def begin_wave(self) -> None:
        if self._open:  # a step an exception cut, or never harvested
            self._pop()
        self._in_step = False
        ident = threading.get_ident()
        if ident != self._thread_ident:
            self._claim_thread(ident)
        self.boundary()
        self.wave += 1
        # the step itself is no phase: time under it alone stays with
        # whatever was running (until ``enter``)
        self._push(self._phase, self._profiler.StepTraceAnnotation(
            "seldon.wave", step_num=self.wave))
        self._in_step = True

    def enter(self, phase: str, **stats: Any) -> None:
        """End the wave's current phase and begin ``phase``."""
        self._close(1 if self._in_step else 0)
        self._push(phase, self._profiler.TraceAnnotation(
            "seldon.wave." + phase, **stats))

    def sub(self, part: str) -> None:
        """The next part of the innermost tiled phase (``prefill``,
        ``launch``) begins: ``seldon.wave.<phase>.<part>``."""
        phase, dot, _ = self._open[-1][0].partition(".")
        if dot:  # the part before it ends here
            self._close(len(self._open) - 1)
        name = f"{phase}.{part}"
        inner = self._profiler.TraceAnnotation("seldon.wave." + name)
        inner.__enter__()
        self._open.append((name, inner))
        self._account(name)

    def stats(self, **stats: Any) -> None:
        """Work counted after the innermost phase began, onto its
        annotation (not onto the part of it that is open)."""
        for name, annotation in reversed(self._open):
            if "." not in name:
                annotation.set_metadata(**stats)
                return

    def begin_prefill(self, **stats: Any) -> None:
        """One prefill group, nested in the phase that runs it."""
        self._push("prefill", self._profiler.TraceAnnotation(
            "seldon.wave.prefill", **stats))

    def end_prefill(self) -> None:
        for depth in range(len(self._open) - 1, -1, -1):
            if self._open[depth][0] == "prefill":
                self._pop(keep=depth)
                return

    def end_wave(self, more: bool) -> None:
        """Close whatever the wave left open (an exception may have cut
        it anywhere).  ``more`` False: the engine has no work, so what
        follows is waiting for a request (``no_work``) and no host gap."""
        self._pop(then="between" if more else "no_work")
        self._in_step = False
        if not more:
            self._gap_open = False

    # ---- the device's side ---------------------------------------------

    def drained(self, upto: Optional[int] = None) -> None:
        """A blocking readback of dispatch ``upto``'s output returned
        (None: of the newest).  Nothing is in flight if no program was
        dispatched after it; otherwise the device has its next program
        queued and no gap opens."""
        if upto is None or upto == self.seq:
            self._gap_open = True
            self._mark = self._clock()

    def dispatched(self, out: Any = None) -> int:
        """A program of the wave loop has been enqueued; its number.
        ``out``: its smallest output that is not donated onward, for the
        device clock to wait on (None where it has none)."""
        self.seq += 1
        now = self._clock()
        if self._gap_open:
            self._gap_s += now - self._mark
            self._gap_open = False
        transitions, self._transitions = self._transitions, []
        self.device.watch(now, out, transitions)
        return self.seq

    @property
    def host_gap_s(self) -> float:
        """Seconds with work and nothing in flight, readback to next
        dispatch.  Blind since PR 29 wherever a chunk is enqueued ahead;
        see ``device_idle_s``."""
        return self._gap_s

    def phase_walls(self) -> Dict[str, float]:
        """Wall seconds of the engine thread by phase, the open phase's
        time so far included: they sum to the time since the seam was
        made, whichever thread asks and whenever."""
        walls, phase, since = self._walls
        walls = dict(walls)
        walls[phase] += self._clock() - since
        return walls

    # ---- compiles ------------------------------------------------------

    def _claim_thread(self, ident: int) -> None:
        """The wave loop runs on this thread: a compile that fires on it
        is booked to the seam's open phase and wave."""
        ref = weakref.ref(self)

        def context() -> Optional[Tuple[str, int]]:
            seam = ref()
            return None if seam is None else (seam._phase, seam.wave)

        _jitwatch.compile_context(ident, context)
        self._thread_ident = ident

    def compiles(self) -> Tuple[int, float]:
        """(backend compiles, their seconds) of the process since this
        engine was built."""
        count, seconds = _jitwatch.compile_totals()
        return count - self._compiles_base[0], seconds - self._compiles_base[1]

    # ---- the profile window --------------------------------------------

    def arm(self, seconds: float) -> Dict[str, Any]:
        """Ask for a window of ``seconds``; it opens at the next wave
        boundary.  409 with no directory to write to (the safe default
        for a profiler on a serving process) or a window already under
        way."""
        from seldon_core_tpu.runtime.component import MicroserviceError

        if not 0.0 < seconds <= 600.0:
            raise MicroserviceError(
                f"profile window of {seconds!r} s: give 0 < seconds <= 600",
                status_code=400, reason="BAD_REQUEST",
            )
        with self._profile_lock:
            if not self._profile_dir:
                raise MicroserviceError(
                    "SELDON_TPU_PROFILE_DIR is not set: this process "
                    "writes no profiles", status_code=409,
                    reason="PROFILE_DISABLED",
                )
            if self._profile["state"] in ("armed", "tracing"):
                raise MicroserviceError(
                    f"a profile window is {self._profile['state']}",
                    status_code=409, reason="PROFILE_BUSY",
                )
            self._profile = {
                "state": "armed", "dir": self._profile_dir,
                "seconds": float(seconds),
            }
            return dict(self._profile)

    def profile_status(self) -> Dict[str, Any]:
        with self._profile_lock:
            return dict(self._profile)

    def boundary_due(self) -> bool:
        """Whether the next boundary opens or closes a window: its
        snapshot is exact only with every launched wave harvested."""
        prof = self._profile
        return prof["state"] == "armed" or (
            prof["state"] == "tracing"
            and self._monotonic() - prof["t_start"] >= prof["seconds"])

    def boundary(self) -> None:
        """Open an armed window, close one that has run its time.
        Engine thread, between waves; profiler failures end the window,
        never decoding.  The profiler's own calls run outside the lock
        (stopping a trace takes seconds, and ``profile_status`` is asked
        from the server's event loop): only this thread moves a window
        on from ``armed``, and ``arm`` replaces none that is under way."""
        prof = self._profile
        state = prof["state"]
        try:
            if state == "armed":
                self._profiler.start_trace(prof["dir"])
                update = dict(state="tracing", t_start=self._monotonic(),
                              wave_start=self.wave,
                              stats_start=self._engine.engine_stats())
            elif (state == "tracing"
                  and self._monotonic() - prof["t_start"] >= prof["seconds"]):
                update = dict(t_stop=self._monotonic(), wave_stop=self.wave,
                              stats_stop=self._engine.engine_stats())
                self._profiler.stop_trace()
                update["state"] = "done"
            else:
                return
        except Exception as exc:  # noqa: BLE001 — profiler failures never stop decoding
            logger.exception("profile window failed")
            update = dict(state="failed", error=f"{type(exc).__name__}: {exc}")
        with self._profile_lock:
            prof.update(update)


class _DeliveryTally:
    """A token event's way out, summed where the consumers' threads
    stand: from ``_stream_push``'s stamp to the return of the
    transport's write, how many events, and how many of them found
    their stream's NEXT event queued already when they were picked up
    (the consumer is a whole wave behind).  Its own lock: the engine
    thread never takes it, ``engine_stats()`` reads under it."""

    __slots__ = ("_lock", "lag_s", "events", "behind")

    def __init__(self):
        self._lock = threading.Lock()
        self.lag_s = 0.0
        self.events = 0
        self.behind = 0

    def add(self, lag_s: float, behind: bool) -> None:
        with self._lock:
            self.lag_s += max(0.0, lag_s)
            self.events += 1
            self.behind += int(behind)

    def read(self) -> Tuple[float, int, int]:
        with self._lock:
            return self.lag_s, self.events, self.behind
