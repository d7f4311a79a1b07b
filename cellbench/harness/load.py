"""The load generator: streams requests at the server from this one process
and times them on the client's clock.

Open loop: every session is due at a time fixed by the seed, whatever the
server does; a request is timed FROM ITS DUE TIME (a stall makes later
requests wait, and that wait counts), and how late the generator itself
ran (sent - due) is recorded beside it. Closed loop: a fixed fleet of
clients, each sending its next request when the last is answered.

All requests stream (`POST /generate`, NDJSON): the first event is the
first token's arrival, each later event carries `tokens_so_far`, and the
last line is the program's result envelope."""

from __future__ import annotations

import dataclasses
import http.client
import json
import threading
import time
from typing import Optional

from harness.traffic_lib import Request


@dataclasses.dataclass
class Result:
    request: Request
    due: float  # monotonic clock; for a closed loop: the moment of sending
    sent: float = 0.0
    first: Optional[float] = None  # first stream event
    done: Optional[float] = None  # final line
    events: list = dataclasses.field(default_factory=list)  # [(t, tokens_so_far)]
    tokens: int = 0
    prompt_tokens: int = 0
    cached_tokens: int = 0
    text: str = ""  # the generated text (the final line's "response")
    deltas: list = dataclasses.field(default_factory=list)  # [(tokens_so_far, new text)]
    status: str = "inflight"
    error: str = ""

    @property
    def ok(self) -> bool:
        return self.status == "success"


class Fleet:
    """Shared state of one run's requests: results, open connections (so
    that what is still running at the end can be cancelled), a stop flag."""

    def __init__(self, host: str, port: int, timeout_s: float = 300.0):
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self.results: list = []
        self.stop = threading.Event()
        self._lock = threading.Lock()
        self._open: set = set()
        self._inflight = 0
        self.threads: list = []
        self.offer_until = float("inf")  # no turn is sent that is due later

    def inflight(self) -> int:
        return self._inflight

    def send(self, req: Request, due: float) -> Result:
        res = Result(request=req, due=due)
        with self._lock:
            self.results.append(res)
            self._inflight += 1
        conn = http.client.HTTPConnection(self.host, self.port, timeout=self.timeout_s)
        try:
            body = json.dumps({
                "prompt": req.prompt, "max_tokens": req.max_tokens,
                "greedy": req.greedy, "temperature": req.temperature,
                "chat": False, "stream": True, **req.fields,
            })
            with self._lock:
                self._open.add(conn)
            if self.stop.is_set():  # the run ended while this one was being built
                res.status = "cancelled"
                return res
            res.sent = time.monotonic()
            conn.request("POST", "/generate", body, {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                res.status = f"http_{resp.status}"
                res.error = resp.read(300).decode(errors="replace")
                return res
            final = None
            while True:
                line = resp.readline()
                if not line:
                    break
                now = time.monotonic()
                ev = json.loads(line)
                if res.first is None:
                    res.first = now
                if ev.get("done"):
                    final = ev
                    res.done = now
                    break
                res.events.append((now, int(ev.get("tokens_so_far", 0))))
                res.deltas.append((int(ev.get("tokens_so_far", 0)), str(ev.get("delta", ""))))
            if final is None:
                res.status, res.error = "truncated", "stream ended without a final line"
                return res
            res.status = final.get("status", "no_status")
            res.error = str(final.get("error", ""))[:200]
            res.tokens = int(final.get("tokens_generated", 0) or 0)
            res.prompt_tokens = int(final.get("prompt_tokens", 0) or 0)
            res.cached_tokens = int(final.get("prefix_cached_tokens", 0) or 0)
            res.text = str(final.get("response", ""))
            res.events.append((res.done, res.tokens))
        except (OSError, http.client.HTTPException, ValueError) as e:
            res.status = "cancelled" if self.stop.is_set() else "error"
            res.error = f"{type(e).__name__}: {e}"[:200]
        finally:
            with self._lock:
                self._open.discard(conn)
                self._inflight -= 1
            conn.close()
        return res

    def cancel_open(self):
        """Close every connection still open: the server cancels a request
        whose client has gone."""
        self.stop.set()
        with self._lock:
            conns = list(self._open)
        for c in conns:
            try:
                if c.sock is not None:
                    c.sock.shutdown(2)
            except OSError:
                pass

    def join(self, timeout_s: float):
        """Wait for every load thread, cancelling again what a thread opened
        after the last cancel; returns the threads that never ended."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            alive = [t for t in self.threads if t.is_alive()]
            if not alive:
                return []
            self.cancel_open()
            alive[0].join(0.5)
        return [t for t in self.threads if t.is_alive()]

    def spawn(self, fn, *args):
        t = threading.Thread(target=fn, args=args, daemon=True)
        self.threads.append(t)
        t.start()
        return t


def _sleep_until(t: float, stop: threading.Event) -> bool:
    """Sleep to monotonic time t; False if the run stopped first."""
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return not stop.is_set()
        if stop.wait(min(left, 0.5)):
            return False


def run_open(fleet: Fleet, sessions: list, t0: float):
    """Dispatch every session at t0 + due_s (a thread each; its turns
    follow in the same thread). Returns when the last session is started
    or the run has stopped."""

    def session_thread(sess):
        due = t0 + sess.due_s
        for k, req in enumerate(sess.turns):
            if due > fleet.offer_until or not _sleep_until(due, fleet.stop):
                return
            res = fleet.send(req, due)
            if not res.ok:
                return  # a failed turn ends its session
            due = res.done + sess.think_s[k]

    def dispatcher():
        for sess in sessions:
            if not _sleep_until(t0 + sess.due_s - 0.002, fleet.stop):
                return
            fleet.spawn(session_thread, sess)

    return fleet.spawn(dispatcher)


def run_closed(fleet: Fleet, plan, t0: float):
    """`plan.clients` callers, each in a loop until the run stops."""

    def client_thread(c):
        k = 0
        _sleep_until(t0 + 0.01 * c, fleet.stop)  # do not open 48 sockets in one instant
        while not fleet.stop.is_set():
            res = fleet.send(plan.request(c, k), time.monotonic())
            k += 1
            if not res.ok and not fleet.stop.is_set():
                time.sleep(0.2)  # a refused request must not become a busy loop

    for c in range(plan.clients):
        fleet.spawn(client_thread, c)
