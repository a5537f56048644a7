"""How long the process that holds the chip went unserved, phase by phase.

A cluster worker tells the head it is alive from its IO loop, once a second.
The head takes a worker for dead after `health_check_period_s` x
`health_check_failure_threshold` seconds (2 x 5 by default) without a
heartbeat, fences it, and hands its chips to a new one.  A call that holds the
interpreter lock that long (or anything that blocks the IO loop) therefore
costs the job a worker group.  This watch measures both from inside the
worker: a thread that asks to run every quarter of a second and notes how
late it was served (the lock), and a task on the IO loop whose last beat the
thread reads (the loop).  It leaves a trail on disk as it goes, so that a
worker that is killed still says where it was."""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Any, Dict, List, Optional

TICK_S = 0.25
NOTE_S = 0.5   # a stall this long gets a line of its own in the trail
ALIVE_S = 2.0  # and the trail says "alive" this often when nothing stalls


class StallWatch:
    def __init__(self, trail_path: Optional[str] = None):
        self.t0 = time.monotonic()
        self.phase = "start"
        self.marks: List[tuple] = [(0.0, "start")]
        self.worst: Dict[str, Dict[str, float]] = {}
        self._trail = open(trail_path, "w", buffering=1) if trail_path else None
        self._stop = threading.Event()
        self._loop_seen: Optional[float] = None
        loop = self._io_loop()
        if loop is not None:
            self._loop_seen = time.monotonic()
            asyncio.run_coroutine_threadsafe(self._beat(), loop)
        self._say(f"start wall={time.time():.3f} loop={'yes' if loop is not None else 'no'}")
        self._thread = threading.Thread(target=self._run, name="bench-stallwatch", daemon=True)
        self._thread.start()

    @staticmethod
    def _io_loop():
        """The loop this worker's heartbeats are sent from, if it is a worker."""
        try:
            from cluster_anywhere_tpu.core.worker import global_worker

            loop = getattr(global_worker(), "loop", None)
            return loop if loop is not None and loop.is_running() else None
        except Exception:
            return None

    async def _beat(self) -> None:
        while not self._stop.is_set():
            self._loop_seen = time.monotonic()
            await asyncio.sleep(TICK_S)

    def _say(self, text: str) -> None:
        if self._trail is not None:
            self._trail.write(f"{time.monotonic() - self.t0:9.3f} {self.phase:14s} {text}\n")

    def _run(self) -> None:
        last = said = time.monotonic()
        while not self._stop.wait(TICK_S):
            now = time.monotonic()
            threads = max(0.0, now - last - TICK_S)
            loop = max(0.0, now - self._loop_seen - TICK_S) if self._loop_seen is not None else 0.0
            last = now
            w = self.worst.setdefault(self.phase, {"threads_s": 0.0, "loop_s": 0.0})
            w["threads_s"] = max(w["threads_s"], threads)
            w["loop_s"] = max(w["loop_s"], loop)
            if threads > NOTE_S or loop > NOTE_S:
                self._say(f"stall threads={threads:.2f} loop={loop:.2f}")
                said = now
            elif now - said > ALIVE_S:
                self._say("alive")
                said = now

    def mark(self, phase: str) -> None:
        self.phase = phase
        self.marks.append((time.monotonic() - self.t0, phase))
        self._say("mark")

    def report(self) -> Dict[str, Any]:
        worst = {k: dict(v) for k, v in self.worst.items()}
        return {
            "marks": list(self.marks), "worst": worst,
            "max_s": max([max(v.values()) for v in worst.values()] or [0.0]),
        }

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        if self._trail is not None:
            self._say("stop")
            self._trail.close()
