"""Load generation and its arithmetic: percentiles, and one selector-driven
HTTP client that keeps every stream of a run open from a single thread.

Percentile arithmetic and the per-request fields follow the program's
``scripts/load_gen.py`` (nearest rank on the sorted sample; status, tokens,
the server's own ``ttft_ms`` / ``queue_ms``). New here: open-loop schedules,
latency counted from the instant a request was *due*, the generator's own
lateness, and token timestamps taken where a user would see them, on receipt
of each server-sent event.
"""

from __future__ import annotations

import json
import selectors
import socket
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile: the value at index floor(p * n) of the sorted
    sample, the last one when that runs off the end. None for no sample."""
    if len(values) == 0:
        return None
    vals = np.sort(np.asarray(values, dtype=np.float64))
    return float(vals[min(len(vals) - 1, int(p * len(vals)))])


def highest_supported_percentile(n: int, beyond: int = 10) -> float:
    """The highest percentile a sample of n supports: one with at least
    ``beyond`` samples above it. 0.0 when even the median has not."""
    if n <= 2 * beyond:
        return 0.0
    return 1.0 - beyond / n


class _Stream:
    """One in-flight streamed request."""

    __slots__ = ("rec", "sock", "out", "buf", "headers_done", "status")

    def __init__(self, rec, sock, out):
        self.rec, self.sock, self.out = rec, sock, out
        self.buf = b""
        self.headers_done = False
        self.status = 0


class StreamClient:
    """Sends ``POST /generate`` with ``"stream": true`` and reads the
    server-sent events of every open request from one thread.

    ``launch(body, due)`` records ``due`` (the instant the schedule wanted the
    request out) and ``sent``; ``pump(timeout)`` moves bytes and stamps each
    token event with the clock at receipt. A finished request's record has
    ``status``, ``token_times``, ``token_ids``, ``final`` (the server's last
    event) and ``error``.
    """

    def __init__(self, host: str, port: int, clock: Callable[[], float] = time.perf_counter):
        self.addr = (host, port)
        self.clock = clock
        self.sel = selectors.DefaultSelector()
        self.open: Dict[int, _Stream] = {}
        self.done: List[Dict[str, Any]] = []

    def launch(self, body: Dict[str, Any], due: float, tag: Any = None) -> Dict[str, Any]:
        payload = json.dumps({**body, "stream": True}).encode()
        head = (f"POST /generate HTTP/1.1\r\nHost: {self.addr[0]}\r\n"
                f"Content-Type: application/json\r\nContent-Length: {len(payload)}\r\n"
                f"Connection: close\r\n\r\n").encode()
        rec = {"tag": tag, "due": due, "sent": self.clock(), "status": 0,
               "token_times": [], "token_ids": [], "final": None, "error": None,
               "end": None}
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setblocking(False)
        try:
            sock.connect_ex(self.addr)
        except OSError as e:
            rec["error"] = f"connect: {e}"
            rec["end"] = self.clock()
            self.done.append(rec)
            sock.close()
            return rec
        st = _Stream(rec, sock, head + payload)
        self.open[sock.fileno()] = st
        self.sel.register(sock, selectors.EVENT_WRITE, st)
        return rec

    def _finish(self, st: _Stream, error: Optional[str] = None) -> None:
        try:
            self.sel.unregister(st.sock)
        except (KeyError, ValueError):
            pass
        self.open.pop(st.sock.fileno(), None)
        st.sock.close()
        rec = st.rec
        rec["end"] = self.clock()
        rec["status"] = st.status
        if error and not rec["error"]:
            rec["error"] = error
        if rec["final"] is None and not rec["error"]:
            rec["error"] = f"stream ended without a final event (status {st.status})"
        self.done.append(rec)

    def _parse(self, st: _Stream, now: float) -> None:
        if not st.headers_done:
            if b"\r\n\r\n" not in st.buf:
                return
            head, st.buf = st.buf.split(b"\r\n\r\n", 1)
            try:
                st.status = int(head.split(b" ", 2)[1])
            except (IndexError, ValueError):
                st.status = -1
            st.headers_done = True
        if st.status != 200:
            return  # an error body is JSON with a Content-Length; read to EOF
        while b"\n\n" in st.buf:
            raw, st.buf = st.buf.split(b"\n\n", 1)
            if not raw.startswith(b"data: "):
                continue
            ev = json.loads(raw[6:])
            if ev.get("done"):
                st.rec["final"] = ev
                if ev.get("error"):
                    st.rec["error"] = str(ev["error"])
            elif "token" in ev:
                st.rec["token_times"].append(now)
                st.rec["token_ids"].append(int(ev["token"]))

    def pump(self, timeout: float) -> None:
        """Wait up to ``timeout`` for socket events and handle them."""
        if not self.open:
            if timeout > 0:
                time.sleep(timeout)
            return
        for key, mask in self.sel.select(max(timeout, 0.0)):
            st: _Stream = key.data
            try:
                if mask & selectors.EVENT_WRITE:
                    err = st.sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
                    if err:
                        self._finish(st, f"connect: errno {err}")
                        continue
                    sent = st.sock.send(st.out)
                    st.out = st.out[sent:]
                    if not st.out:
                        self.sel.modify(st.sock, selectors.EVENT_READ, st)
                    continue
                chunk = st.sock.recv(65536)
            except (BlockingIOError, InterruptedError):
                continue
            except OSError as e:
                self._finish(st, f"socket: {e}")
                continue
            now = self.clock()
            if chunk:
                st.buf += chunk
                self._parse(st, now)
                continue
            if st.status != 200 and st.headers_done:
                try:
                    detail = json.loads(st.buf or b"{}").get("error", "")
                except ValueError:
                    detail = ""
                self._finish(st, f"HTTP {st.status} {detail}".strip())
            else:
                self._finish(st)

    def abandon(self) -> List[Dict[str, Any]]:
        """Close what is still open (the window shut on it); returns those
        records, marked unfinished and not counted as failures."""
        left = []
        for st in list(self.open.values()):
            try:
                self.sel.unregister(st.sock)
            except (KeyError, ValueError):
                pass
            st.sock.close()
            st.rec["end"] = None
            left.append(st.rec)
        self.open.clear()
        return left

    def close(self) -> None:
        self.abandon()
        self.sel.close()


def http_get_json(host: str, port: int, path: str, timeout: float = 10.0) -> Dict[str, Any]:
    import urllib.request

    with urllib.request.urlopen(f"http://{host}:{port}{path}", timeout=timeout) as r:
        return json.loads(r.read())


def inter_token_gaps_ms(records: Sequence[Dict[str, Any]]) -> List[float]:
    gaps: List[float] = []
    for r in records:
        t = r["token_times"]
        gaps.extend(1e3 * (b - a) for a, b in zip(t, t[1:]))
    return gaps
