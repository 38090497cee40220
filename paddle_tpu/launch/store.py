"""TCPStore: key-value rendezvous for multi-host bootstrap.

Reference: paddle/fluid/distributed/store/tcp_store.cc (TCPStore, Store) —
the blocking KV store every ProcessGroup bootstraps through.

TPU redesign: same wire idea (tiny length-prefixed TCP protocol with
set/get/wait/add/delete/compare_set), implemented over a threaded
socketserver on the master host. jax's own coordination service still
bootstraps the device runtime; this store carries the *launcher-level*
protocol — rank assignment, peer discovery, elastic heartbeats — the part
the reference does with HTTPMaster/ETCDMaster + TCPStore.

A C++ implementation of the same protocol lives in
``paddle_tpu/native/pdtpu_native.cpp`` (built as ``build/libpdtpu_native.so``
via ``make -C native``); ``TCPStore`` uses its server through
ctypes (paddle_tpu.runtime_native) when built, falling back to the pure
Python socketserver here.
"""

from __future__ import annotations

import socket
import socketserver
import struct
import threading
import time
from typing import Dict, Optional

from ..resilience import _state as _rs_state

_OPS = {"set": 0, "get": 1, "add": 2, "wait": 3, "delete": 4, "cas": 5,
        "list": 6}


def _pack(*fields: bytes) -> bytes:
    out = [struct.pack("<I", len(fields))]
    for f in fields:
        out.append(struct.pack("<I", len(f)))
        out.append(f)
    return b"".join(out)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("store peer closed")
        buf += chunk
    return buf


def _unpack(sock: socket.socket):
    (nf,) = struct.unpack("<I", _recv_exact(sock, 4))
    fields = []
    for _ in range(nf):
        (ln,) = struct.unpack("<I", _recv_exact(sock, 4))
        fields.append(_recv_exact(sock, ln))
    return fields


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        srv: "_StoreServer" = self.server  # type: ignore[assignment]
        with srv._cv:
            srv._live.add(self.request)
        try:
            while True:
                fields = _unpack(self.request)
                op = fields[0].decode()
                resp = srv.dispatch(op, fields[1:])
                self.request.sendall(_pack(*resp))
        except (ConnectionError, OSError):
            return
        finally:
            with srv._cv:
                srv._live.discard(self.request)


class _StoreServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True
    # the native server's backlog; socketserver's 5 overflows when a handful
    # of clients connect at once, and each dropped SYN costs its client 1 s
    request_queue_size = 128

    def __init__(self, addr):
        super().__init__(addr, _Handler)
        self._kv: Dict[str, bytes] = {}
        self._cv = threading.Condition()
        self._live: set = set()     # accepted connections, under _cv

    def stop(self):
        """Stop accepting, then hang up on every live connection — what the
        native server's Stop() does: a closed store answers nobody."""
        self.shutdown()
        self.server_close()
        with self._cv:
            for conn in self._live:
                try:
                    conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass

    def dispatch(self, op: str, args):
        with self._cv:
            if op == "set":
                self._kv[args[0].decode()] = args[1]
                self._cv.notify_all()
                return [b"ok"]
            if op == "get":
                v = self._kv.get(args[0].decode())
                return [b"ok", v] if v is not None else [b"miss"]
            if op == "add":
                k = args[0].decode()
                cur = int(self._kv.get(k, b"0")) + int(args[1])
                self._kv[k] = str(cur).encode()
                self._cv.notify_all()
                return [b"ok", str(cur).encode()]
            if op == "delete":
                existed = self._kv.pop(args[0].decode(), None) is not None
                self._cv.notify_all()
                return [b"ok" if existed else b"miss"]
            if op == "cas":
                k = args[0].decode()
                if self._kv.get(k) == args[1] or (args[1] == b"" and k not in self._kv):
                    self._kv[k] = args[2]
                    self._cv.notify_all()
                    return [b"ok", args[2]]
                return [b"miss", self._kv.get(k, b"")]
            if op == "list":
                prefix = args[0].decode()
                ks = [k for k in self._kv if k.startswith(prefix)]
                return [b"ok"] + [k.encode() for k in sorted(ks)]
            if op == "wait":
                k = args[0].decode()
                deadline = time.monotonic() + float(args[1])
                while k not in self._kv:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return [b"timeout"]
                    self._cv.wait(remaining)
                return [b"ok", self._kv[k]]
        raise ValueError(f"bad store op {op!r}")


class TCPStore:
    """Client (and, on the master, embedded server) for the rendezvous store.

    ``TCPStore(addr, is_master=True)`` starts the server thread; every
    process (master included) talks to it through a client socket, like the
    reference where rank 0 hosts the store in-process.

    ``retry`` (a ``resilience.RetryPolicy``) makes ``set``/``get`` —
    and the control-plane ops ``add``/``delete``/``compare_set``/
    ``keys`` — survive transient socket failures: a failed op
    reconnects the client socket and re-attempts under the policy (a
    blip in the master's network must cost a heartbeat, not the job;
    a bounced controller must cost a serving worker one retry, not its
    lease mid-epoch).  ``store.set`` / ``store.get`` are registered
    fault-injection sites; the mutating control ops fire ``store.set``
    and ``keys`` fires ``store.get``.  ``compare_set`` is made
    reconnect-idempotent: a retried CAS whose FIRST attempt applied
    server-side (the reply died with the socket) reports success when
    the key now holds the desired value, so a lease-renew chain never
    breaks on its own ghost write.  ``wait`` is deliberately NOT
    retried — its timeout is an answer, not a transient.

    ``set``/``get`` also take a per-call ``timeout=`` override on the
    client socket: one store serves both sub-second heartbeats and
    multi-megabyte KV-page transfer chunks (``serving/disagg.py``), and
    the big payloads need a longer deadline than the liveness probes
    without reconfiguring (or duplicating) the store client.
    """

    def __init__(self, endpoint: str, is_master: bool = False,
                 timeout: float = 60.0, native: Optional[bool] = None,
                 retry=None):
        self.retry = retry
        host, port = endpoint.rsplit(":", 1)
        self.endpoint = endpoint
        self.timeout = timeout
        self._server = None
        self._native_server = None
        if is_master:
            use_native = native
            if use_native is None:
                from .. import runtime_native
                use_native = runtime_native.available()
            if use_native:
                # C++ server (paddle_tpu/native/pdtpu_native.cpp) — same wire protocol,
                # immune to GIL stalls in the hosting training process
                from ..runtime_native import StoreServer as _Native
                self._native_server = _Native(host, int(port))
                port = str(self._native_server.port)
            else:
                self._server = _StoreServer((host, int(port)))
                port = str(self._server.server_address[1])
                t = threading.Thread(target=self._server.serve_forever,
                                     daemon=True, name="pdtpu-store")
                t.start()
            self.endpoint = f"{host}:{port}"
        self._sock = self._connect(host, int(port))
        self._lock = threading.Lock()

    def _connect(self, host: str, port: int) -> socket.socket:
        deadline = time.monotonic() + self.timeout
        while True:
            try:
                return socket.create_connection((host, port), timeout=self.timeout)
            except OSError:
                if time.monotonic() > deadline:
                    raise TimeoutError(f"cannot reach store at {host}:{port}")
                time.sleep(0.1)

    def _call(self, op: str, *args: bytes, sock_timeout: Optional[float] = None):
        with self._lock:
            if sock_timeout is not None:
                self._sock.settimeout(sock_timeout)
            try:
                self._sock.sendall(_pack(op.encode(), *args))
                return _unpack(self._sock)
            finally:
                if sock_timeout is not None:
                    self._sock.settimeout(self.timeout)

    def _reconnect(self) -> None:
        with self._lock:
            try:
                self._sock.close()
            except OSError:
                pass
            host, port = self.endpoint.rsplit(":", 1)
            self._sock = self._connect(host, int(port))

    def _resilient(self, site: str, fn):
        """Fault-injection check + (optional) retry-with-reconnect around
        one store op.  One falsy check when no injector is installed and
        no policy is configured."""
        def attempt():
            fi = _rs_state.FAULTS[0]
            if fi is not None:
                fi(site)
            try:
                return fn()
            except (ConnectionError, OSError, TimeoutError):
                # the request/response stream is desynchronized (or the
                # socket is dead) — a retry on the same socket would read
                # the wrong reply; reconnect before the next attempt
                try:
                    self._reconnect()
                except OSError:
                    pass   # next attempt's send will surface it
                raise
        if self.retry is None:
            return attempt()
        return self.retry.run(attempt, site=site)

    def set(self, key: str, value: bytes,
            timeout: Optional[float] = None) -> None:
        self._resilient(
            "store.set",
            lambda: self._call("set", key.encode(), value,
                               sock_timeout=timeout))

    def get(self, key: str,
            timeout: Optional[float] = None) -> Optional[bytes]:
        r = self._resilient(
            "store.get",
            lambda: self._call("get", key.encode(),
                               sock_timeout=timeout))
        return r[1] if r[0] == b"ok" else None

    def add(self, key: str, amount: int = 1) -> int:
        # NOTE: add is retried for connectivity, not idempotency — a
        # reply lost to a reconnect may double-apply the increment.
        # Every caller treats the counter as an allocator of unique /
        # monotonic values (barrier arrivals excepted, which never
        # share a socket failure with a healthy barrier), so a skipped
        # value is safe where a dead client socket is not.
        r = self._resilient(
            "store.set",
            lambda: self._call("add", key.encode(), str(amount).encode()))
        return int(r[1])

    def delete(self, key: str) -> bool:
        r = self._resilient(
            "store.set", lambda: self._call("delete", key.encode()))
        return r[0] == b"ok"

    def compare_set(self, key: str, expect: bytes, value: bytes) -> bool:
        r = self._resilient(
            "store.set",
            lambda: self._call("cas", key.encode(), expect, value))
        if r[0] == b"ok":
            return True
        # Reconnect idempotency: if an earlier attempt applied but its
        # reply died with the socket, the retried CAS sees expect-
        # mismatch with the key already holding OUR value — that is a
        # success, not a conflict (lease renewal chains CAS on the
        # previous value, so a ghost write must not drop the lease).
        return len(r) > 1 and r[1] == value and value != expect

    def keys(self, prefix: str = "") -> list:
        r = self._resilient(
            "store.get", lambda: self._call("list", prefix.encode()))
        return [k.decode() for k in r[1:]]

    def wait(self, key: str, timeout: Optional[float] = None) -> bytes:
        # The server holds the request for up to `timeout`, so the client
        # socket must outlive the server-side wait or the reply would land
        # in the buffer after a socket timeout and desynchronize the
        # request/response stream for every later call.
        server_timeout = timeout if timeout is not None else self.timeout
        r = self._call("wait", key.encode(), str(server_timeout).encode(),
                       sock_timeout=server_timeout + 10.0)
        if r[0] != b"ok":
            raise TimeoutError(f"store key {key!r} not set in time")
        return r[1]

    def barrier(self, name: str, world_size: int,
                timeout: Optional[float] = None) -> None:
        """All-process barrier via an arrival counter + release key."""
        n = self.add(f"__barrier/{name}/count", 1)
        if n == world_size:
            self.set(f"__barrier/{name}/go", b"1")
        self.wait(f"__barrier/{name}/go", timeout)

    def close(self) -> None:
        try:
            self._sock.close()
        finally:
            if self._server is not None:
                self._server.stop()
                self._server = None
            if self._native_server is not None:
                self._native_server.close()
                self._native_server = None


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]
