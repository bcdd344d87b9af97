"""The load driver: one thread that sends a mix's requests to ``submit``.

A closed loop (a mix with ``clients``): each client sends its next request
when its last one completes, and the request is *due* at the completion
that released its client. An open loop (a mix with ``arrivals``): request
k is due at ``t0`` plus its scheduled offset (``Stream.due``) and is sent
then, whatever the server is doing. Each request is timed from when it was
due. The time it was actually sent is kept beside it, so a driver that ran
late shows as lateness and not as a slow server.
"""
from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
from typing import Callable, List, Optional

import numpy as np

clock = time.perf_counter


@dataclasses.dataclass
class Sent:
    index: int                  # request k of the stream
    client: int                 # -1 in an open loop
    due: float
    prompt: np.ndarray
    max_new: int
    submit: float = math.nan    # when ``submit`` was called
    request: object = None      # the engine's Request
    error: Optional[BaseException] = None

    @property
    def done(self) -> bool:
        return self.error is not None or (
            self.request is not None and self.request.future.done())

    @property
    def failed(self) -> bool:
        if self.error is not None:
            return True
        f = self.request.future if self.request is not None else None
        return f is not None and f.done() and f.exception() is not None


class Driver:
    """Sends the requests of ``stream`` (a ``traffic.Stream``) through
    ``submit(tokens, max_new_tokens=, eos_id=)`` on a thread of its own."""

    def __init__(self, mix: dict, stream, submit: Callable):
        self.mix, self.stream, self._submit = mix, stream, submit
        self.sent: List[Sent] = []
        self._stop = threading.Event()
        self._done: "queue.SimpleQueue[Sent]" = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None
        self.t0 = math.nan

    def start(self, t0: float) -> "Driver":
        self.t0 = t0
        loop = self._open if "arrivals" in self.mix else self._closed
        self._thread = threading.Thread(target=loop,
                                        name="chipbench-load",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Send nothing more; requests already sent run on."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                raise RuntimeError("load driver did not stop")
        self._submit = None             # the served path may now be freed

    def drain(self, timeout: float) -> bool:
        """Wait until every sent request has completed or failed."""
        deadline = clock() + timeout
        for s in self.sent:
            if s.request is None:
                continue
            left = deadline - clock()
            if left <= 0:
                return all(x.done for x in self.sent)
            try:
                s.request.future.exception(timeout=left)
            except Exception:       # a timeout: checked below
                pass
        return all(x.done for x in self.sent)

    def _send(self, k: int, client: int, due: float,
              max_new: Optional[int] = None) -> Sent:
        tokens, new = self.stream.request(k)
        s = Sent(index=k, client=client, due=due, prompt=tokens,
                 max_new=new if max_new is None else max_new)
        self.sent.append(s)
        s.submit = clock()
        try:
            s.request = self._submit(tokens, max_new_tokens=s.max_new,
                                     eos_id=-1)
        except Exception as exc:    # refused: counts as failed, not lost
            s.error = exc
            self._done.put(s)
            return s
        # the callback holds the queue alone: a future outlives the run in
        # the record, and must not keep the served path (and its weights)
        # alive through the driver
        done = self._done
        s.request.future.add_done_callback(lambda _f, s=s: done.put(s))
        return s

    def _open(self) -> None:
        k = 0
        while True:
            due = self.t0 + self.stream.due(k)
            if self._stop.wait(max(0.0, due - clock())):
                break
            self._send(k, -1, due)
            k += 1

    def _closed(self) -> None:
        clients = int(self.mix["clients"])
        # each client's first request asks for a share of its output, so
        # completions are spread from the start and not all in one step
        shares = self.stream.first_shares(clients)
        k = 0
        for c in range(clients):
            _, new = self.stream.request(k)
            self._send(k, c, self.t0, max(1, int(round(shares[c] * new))))
            k += 1
        while not self._stop.is_set():
            try:
                s = self._done.get(timeout=0.05)
            except queue.Empty:
                continue
            if self._stop.is_set():
                break
            r = s.request
            due = r.done_t if (r is not None and r.done_t is not None
                               and not s.failed) else clock()
            self._send(k, s.client, due)
            k += 1
