"""Host transport for the serving ring: stdlib HTTP/JSON host server +
client, SIGTERM drain, and the subprocess host entrypoint.

`HostServer` puts ONE ring host on the network: today's ServeFleet as the
local slice behind a `ThreadingHTTPServer` (the exact telemetry/export.py
OpsServer idiom — daemon thread, loopback default, port 0 = ephemeral, no
new deps). The wire format is JSON with base64 float32 arrays, so a
render round-trips BITWISE (tests/test_serve_ring.py pins HTTP == local):

    POST /render   {"image_id", "pose": [16 row-major floats], "tier",
                    "deadline_ms", "image": {shape,dtype,b64} | null}
                -> {"ok": true, "rgb": {...}, "depth": {...}}
                   or an error envelope {"ok": false, "kind", "error"}
                   (429 shed, 504 deadline, 503 draining — the client
                   re-raises the matching exception class, so admission
                   semantics survive the wire)
    GET  /healthz  fleet health + {"host", "state", "inflight"}
    GET  /stats    fleet stats + AOT boot evidence (bucket_loads/compiles)
    GET  /metrics  Prometheus text of this process's registry
    POST /drain    begin draining (the programmatic SIGTERM)

Preemption is ported serve-side from the train loop (train/resilience.py
PreemptionHandler): SIGTERM/SIGINT only flips the sticky flag; a watcher
thread then runs the drain — stop admitting (503), wait out the in-flight
requests (bounded by drain_timeout_s), emit the authoritative
`serve.host_drain` with the host's lifetime owner-hit/remote-route split,
dump a flight-recorder incident bundle when a recorder is armed, and close
the fleet. The key range hands back to the ring the moment any front
observes the 503 (serve/ring.py re-resolves ring-wise).

The transport is WIRE-HARDENED behind `serve.net.*` (all default off;
net-off constructs none of the machinery and stays bitwise-identical,
test-pinned): `NetPolicy` gives the client split connect/read timeouts,
bounded jittered-exponential-backoff retries (safe: a render is a pure
function of key+pose, so at-least-once is idempotent), a per-host
`CircuitBreaker` (closed -> open -> half-open with single-probe
admission, pinned `serve.breaker` events), and deadline propagation —
the budget LEFT rides the `X-Mtpu-Deadline-Left-Ms` header so a host
SWEEPS work the front already expired into the existing DeadlineExceeded
envelope instead of rendering it. Connections are kept alive per thread
(HTTP/1.1 + reconnect-on-stale), and every network fault a test needs —
latency, refusal, mid-response reset, truncation, partition — is
injected through the testing/faults.py net_* seams, never by
monkeypatching this module.

Since PR 20 the JSON wire has a negotiated BINARY sibling (serve/wire.py,
`serve.wire.*` keys, default off): a wire-enabled server advertises
`X-Mtpu-Wire: mtpu-wire1` on every response and accepts
`application/x-mtpu-wire1` batch frames on /render; a wire-enabled client
checks the advertisement once (a /healthz round) and speaks binary —
length-prefixed frames, raw little-endian tensors, f32/bf16/int8 wire
codecs, N coalesced requests per exchange — only to a peer that
advertised, falling back to the byte-identical JSON path otherwise
(counted `serve.wire.fallbacks`). ALL framing, JSON and binary, is built
and parsed by serve/wire.py helpers, so negotiation lives in exactly one
seam; a corrupted/truncated binary frame is rejected by the mtpu-wire1
tripwires and RETRIED like mangled JSON, never crashed on.

`main()` is the deployable unit's entrypoint: boot a host from a PACKED
AOT artifact (tools/aot_warmstore.py --pack) with zero live compiles and
serve until drained. Run `python -m mine_tpu.serve.hostnet --help`.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import random
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from mine_tpu import telemetry
from mine_tpu.analysis.locks import ordered_condition, ordered_lock
from mine_tpu.serve import wire
from mine_tpu.serve.admission import DeadlineExceeded, RequestShed
from mine_tpu.serve.ring import (HOST_ALIVE, HOST_DRAINING, BreakerOpen,
                                 HostUnavailable)
# the JSON tensor wire now lives in serve/wire.py (one framing seam for
# both formats); re-exported here because tools/tests import them from
# hostnet, the historical home
from mine_tpu.serve.wire import pack_array, unpack_array  # noqa: F401
from mine_tpu.testing import faults

# synthetic-host geometry (--synthetic): matches tools/serve_chaos_soak.py
# so the soak's keys/images render identically through subprocess hosts
SYN_S, SYN_HW = 4, 8


def synthetic_encode_fn(img_hwc):
    """The soak's deterministic tiny encoder (image bytes -> fixed MPI),
    shared here so subprocess hosts and in-parent builders produce
    IDENTICAL programs and plane data — the cross-process bitwise and
    zero-compile-join assertions depend on it."""
    rng = np.random.RandomState(int(np.asarray(img_hwc).sum()) % 1000)
    p = rng.uniform(-1, 1, (SYN_S, 4, SYN_HW, SYN_HW)).astype(np.float32)
    return (p[:, 0:3], p[:, 3:4],
            np.linspace(1.0, 0.2, SYN_S, dtype=np.float32),
            np.eye(3, dtype=np.float32))


# wire error envelope <-> exception class: the admission layer's verdicts
# must survive the HTTP hop (a shed best-effort request on a remote host
# is STILL a RequestShed to the front's caller, not a transport error)
_KIND_STATUS = {"RequestShed": 429, "DeadlineExceeded": 504,
                "HostUnavailable": 503}
_KIND_RAISE = {"RequestShed": RequestShed,
               "DeadlineExceeded": DeadlineExceeded,
               "HostUnavailable": HostUnavailable}

# the front's remaining deadline budget, in milliseconds, as seen at send
# time — the server sweeps non-positive values into the 504 envelope
DEADLINE_HEADER = "X-Mtpu-Deadline-Left-Ms"


@dataclasses.dataclass(frozen=True)
class NetPolicy:
    """The serve.net.* knobs as one immutable value (config.py parses the
    keys; serve_cli builds this and hands it to every HostClient and the
    RingFront). `enabled=False` — the default — constructs NONE of the
    hardening: no breaker, no retries, no deadline header, no prober."""

    enabled: bool = False
    connect_timeout_s: float = 5.0   # TCP connect budget (fail fast)
    read_timeout_s: float = 60.0     # response budget (renders are slow)
    retries: int = 2                 # extra attempts after the first
    backoff_ms: float = 20.0         # base of the jittered exponential
    breaker_threshold: int = 5       # consecutive failures -> open
    breaker_reset_s: float = 10.0    # open -> half-open after this long
    probe_interval_s: float = 0.0    # front heartbeat period (0 = off)
    suspect_misses: int = 3          # consecutive probe misses -> suspect
    dead_misses: int = 10            # consecutive REFUSED -> mark_dead
    revive_probes: int = 2           # consecutive oks -> clear suspicion


class CircuitBreaker:
    """Per-host client-side circuit: closed -> open after `threshold`
    consecutive failures, open -> half-open after `reset_s`, half-open
    admits ONE probe at a time — success closes, failure re-opens. State
    transitions emit the pinned `serve.breaker` event and bump
    `serve.net.breaker_<state>`; emits happen AFTER the lock releases
    (the "serve.net.breaker" rank sits below telemetry, see
    analysis/locks.py). `now_fn` is injectable so tests drive the reset
    window with a fake clock."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, host: str, threshold: int, reset_s: float,
                 now_fn=time.monotonic):
        self.host = str(host)
        self.threshold = int(threshold)
        self.reset_s = float(reset_s)
        self._now = now_fn
        self._lock = ordered_lock("serve.net.breaker")
        self.state = self.CLOSED
        self.failures = 0
        self.opens = 0
        self._opened_at = 0.0
        self._probing = False

    def allow(self) -> bool:
        """May a request go to the wire right now?"""
        transition = None
        ok = False
        with self._lock:
            if self.state == self.CLOSED:
                ok = True
            elif self.state == self.OPEN:
                if self._now() - self._opened_at >= self.reset_s:
                    self.state = self.HALF_OPEN
                    self._probing = True
                    transition = self.HALF_OPEN
                    ok = True
            else:  # HALF_OPEN: one probe in flight at a time
                if not self._probing:
                    self._probing = True
                    ok = True
            failures = self.failures
        if transition:
            self._emit(transition, failures)
        return ok

    def record(self, ok: bool) -> None:
        """Feed one wire verdict (every attempt, probe or request)."""
        transition = None
        with self._lock:
            self._probing = False
            if ok:
                if self.state != self.CLOSED:
                    transition = self.CLOSED
                self.state = self.CLOSED
                self.failures = 0
            else:
                self.failures += 1
                if (self.state == self.HALF_OPEN
                        or (self.state == self.CLOSED
                            and self.failures >= self.threshold)):
                    self.opens += 1
                    transition = self.OPEN
                    self.state = self.OPEN
                    self._opened_at = self._now()
            failures = self.failures
        if transition:
            self._emit(transition, failures)

    def _emit(self, state: str, failures: int) -> None:
        telemetry.emit("serve.breaker", host=self.host, state=state,
                       failures=int(failures))
        telemetry.counter(f"serve.net.breaker_{state}").inc()

    def snapshot(self) -> Dict:
        with self._lock:
            return {"state": self.state, "failures": self.failures,
                    "opens": self.opens}


class HostServer:
    """One ring host: a ServeFleet behind the stdlib HTTP/JSON transport.

    Construct bound (port 0 = ephemeral; read `.port`), then `.start()`.
    `drain()` is idempotent and runs the full hand-back sequence; the
    `drained` event fires when it completes (main() exits on it).
    """

    def __init__(self, fleet, host_id: str, port: int = 0,
                 host: str = "127.0.0.1", drain_timeout_s: float = 30.0,
                 recorder=None, wire_policy=None):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.fleet = fleet
        self.host_id = str(host_id)
        self.drain_timeout_s = float(drain_timeout_s)
        self.recorder = recorder
        # serve.wire.*: with a binary WirePolicy the server ADVERTISES
        # mtpu-wire1 on every response and accepts binary batch frames on
        # /render. None (the default) is the exact PR-19 server: no
        # advertisement header, JSON only — byte-identical, test-pinned.
        self.wire = wire_policy if (wire_policy is not None
                                    and wire_policy.binary) else None
        self.draining = False
        self.inflight = 0
        self.requests = 0
        self.swept = 0  # requests the deadline header expired on arrival
        self.drained = threading.Event()
        self._cv = ordered_condition("serve.hostnet.state")
        srv = self

        class _Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 + the always-set Content-Length = keep-alive:
            # the client's per-thread connection survives across
            # renders instead of paying TCP setup on every request
            protocol_version = "HTTP/1.1"

            def _send(self, code: int, body: bytes,
                      ctype: str = "application/json") -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                if srv.wire is not None:
                    # the capability advertisement the client's one-time
                    # negotiation check reads (serve/wire.py)
                    self.send_header(wire.WIRE_HEADER, wire.WIRE_PROTO)
                self.end_headers()
                self.wfile.write(body)

            def _send_json(self, code: int, obj: Dict) -> None:
                self._send(code, (json.dumps(obj) + "\n").encode())

            def do_GET(self):  # noqa: N802 (stdlib handler API)
                path = self.path.split("?", 1)[0]
                try:
                    if path == "/healthz":
                        self._send_json(200, srv.healthz())
                    elif path == "/stats":
                        self._send_json(200, srv.stats())
                    elif path == "/metrics":
                        from mine_tpu.telemetry.export import (
                            CONTENT_TYPE, render_prometheus)
                        self._send(200, render_prometheus().encode(),
                                   CONTENT_TYPE)
                    else:
                        self._send_json(404, {"error": "not found"})
                except BrokenPipeError:
                    pass

            def do_POST(self):  # noqa: N802 (stdlib handler API)
                path = self.path.split("?", 1)[0]
                try:
                    n = int(self.headers.get("Content-Length", 0) or 0)
                    raw_body = self.rfile.read(n)
                    if path == "/render":
                        left = None
                        raw = self.headers.get(DEADLINE_HEADER)
                        if raw is not None:
                            try:
                                left = float(raw)
                            except ValueError:
                                left = None  # malformed = absent
                        ctype = (self.headers.get("Content-Type")
                                 or "").split(";")[0].strip()
                        if (srv.wire is not None
                                and ctype == wire.CTYPE_BINARY):
                            telemetry.counter(
                                "serve.wire.bytes_rx").inc(len(raw_body))
                            code, payload, rctype = \
                                srv._handle_render_wire(
                                    raw_body, deadline_left_ms=left)
                            telemetry.counter(
                                "serve.wire.bytes_tx").inc(len(payload))
                            self._send(code, payload, rctype)
                            return
                        body = json.loads(raw_body or b"{}")
                        code, obj = srv._handle_render(
                            body, deadline_left_ms=left)
                        self._send_json(code, obj)
                        return
                    body = json.loads(raw_body or b"{}")
                    if path == "/drain":
                        # hand back asynchronously: the response must go
                        # out before the fleet starts tearing down
                        threading.Thread(target=srv.drain,
                                         kwargs={"reason": "http"},
                                         daemon=True).start()
                        self._send_json(200, {"ok": True,
                                              "host": srv.host_id})
                    else:
                        self._send_json(404, {"error": "not found"})
                except BrokenPipeError:
                    pass

            def log_message(self, fmt, *args):  # silence request noise
                pass

        self._server = ThreadingHTTPServer((host, int(port)), _Handler)
        self._server.daemon_threads = True
        self.host = host
        self.port = int(self._server.server_address[1])
        self._thread: Optional[threading.Thread] = None

    # -- request path -----------------------------------------------------

    def _handle_render(self, body: Dict, deadline_left_ms=None):
        """The legacy JSON /render: one request, one envelope — behavior
        (and bytes) identical to PR 19; parsing/packing now rides the
        serve/wire.py seam shared with the binary path."""
        if deadline_left_ms is not None and deadline_left_ms <= 0:
            # the front's budget was spent in flight: sweep instead of
            # rendering work nobody is waiting on — same verdict (and
            # client-side exception) as the batcher's own expiry sweep
            with self._cv:
                self.swept += 1
            telemetry.counter("serve.net.deadline_swept").inc()
            return 504, {"ok": False, "kind": "DeadlineExceeded",
                         "error": "deadline spent before host dispatch"}
        with self._cv:
            if self.draining:
                return 503, {"ok": False, "kind": "HostUnavailable",
                             "error": "draining"}
            self.inflight += 1
            self.requests += 1
        deadline_ms = body.get("deadline_ms")
        if deadline_left_ms is not None:
            # the host-local batcher sweeps against whichever budget is
            # tighter: the request's own or what the front has left
            deadline_ms = (min(float(deadline_ms), deadline_left_ms)
                           if deadline_ms else deadline_left_ms)
        try:
            req = wire.json_render_request(body)
            rgb, depth = self.fleet.submit(
                req["image_id"], req["pose"], tier=req["tier"],
                deadline_ms=deadline_ms, image=req["image"]).result()
            return 200, wire.json_render_envelope(
                {"ok": True, "rgb": rgb, "depth": depth})
        except Exception as e:
            kind = type(e).__name__
            return (_KIND_STATUS.get(kind, 500),
                    {"ok": False, "kind": kind, "error": str(e)})
        finally:
            with self._cv:
                self.inflight -= 1
                self._cv.notify_all()

    def _render_core(self, reqs: List[Dict], deadline_left_ms=None):
        """Admission + fleet dispatch for a decoded BATCH, in request
        order. Every admissible request is submitted before any result is
        collected, so an N-request frame rides the fleet's existing
        coalescing (the batcher groups the in-flight set into device
        batches exactly as it does for concurrent single requests).
        Returns one envelope per request — numpy rgb/depth when ok, the
        admission verdict (kind/error) otherwise; a shed or expired item
        never fails its batchmates."""
        out: List[Optional[Dict]] = [None] * len(reqs)
        pending = []
        for i, req in enumerate(reqs):
            if deadline_left_ms is not None and deadline_left_ms <= 0:
                with self._cv:
                    self.swept += 1
                telemetry.counter("serve.net.deadline_swept").inc()
                out[i] = {"ok": False, "kind": "DeadlineExceeded",
                          "error": "deadline spent before host dispatch"}
                continue
            with self._cv:
                if self.draining:
                    out[i] = {"ok": False, "kind": "HostUnavailable",
                              "error": "draining"}
                    continue
                self.inflight += 1
                self.requests += 1
            deadline_ms = req.get("deadline_ms")
            if deadline_left_ms is not None:
                deadline_ms = (min(float(deadline_ms), deadline_left_ms)
                               if deadline_ms else deadline_left_ms)
            try:
                fut = self.fleet.submit(
                    req["image_id"], req["pose"], tier=req.get("tier"),
                    deadline_ms=deadline_ms, image=req.get("image"))
            except Exception as e:
                with self._cv:
                    self.inflight -= 1
                    self._cv.notify_all()
                out[i] = {"ok": False, "kind": type(e).__name__,
                          "error": str(e)}
                continue
            pending.append((i, fut))
        for i, fut in pending:
            try:
                rgb, depth = fut.result()
                out[i] = {"ok": True, "rgb": rgb, "depth": depth}
            except Exception as e:
                out[i] = {"ok": False, "kind": type(e).__name__,
                          "error": str(e)}
            finally:
                with self._cv:
                    self.inflight -= 1
                    self._cv.notify_all()
        return out

    def _handle_render_wire(self, raw: bytes, deadline_left_ms=None):
        """One binary /render exchange: decode the mtpu-wire1 batch frame
        (hostile frames -> a 400 JSON envelope the client treats as
        non-retryable), dispatch through _render_core, and mirror the
        request's codec on the multi-result response frame."""
        t0 = time.monotonic()
        try:
            reqs, codec = wire.decode_render_request(raw)
        except wire.WireError as e:
            telemetry.counter("serve.wire.rejects").inc()
            env = {"ok": False, "kind": "WireError", "error": str(e)}
            return 400, (json.dumps(env) + "\n").encode(), wire.CTYPE_JSON
        telemetry.histogram("serve.wire.decode_ms").record(
            (time.monotonic() - t0) * 1e3)
        envs = self._render_core(reqs, deadline_left_ms=deadline_left_ms)
        t0 = time.monotonic()
        payload = wire.encode_render_response(envs, codec=codec)
        telemetry.histogram("serve.wire.encode_ms").record(
            (time.monotonic() - t0) * 1e3)
        # per-item verdicts travel INSIDE the frame envelopes (the client
        # re-raises typed per item); the HTTP status stays 200 for any
        # well-formed frame
        return 200, payload, wire.CTYPE_BINARY

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "HostServer":
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name=f"mine-tpu-host-{self.host_id}")
        self._thread.start()
        return self

    def drain(self, reason: str = "signal") -> None:
        """The hand-back sequence; idempotent, safe from any thread."""
        with self._cv:
            if self.draining:
                return
            self.draining = True
            deadline = time.monotonic() + self.drain_timeout_s
            while self.inflight > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cv.wait(timeout=min(left, 0.5))
            leftover = self.inflight
        cache = getattr(self.fleet, "cache", None)
        telemetry.emit(
            "serve.host_drain", host=self.host_id, hosts=0,
            inflight=leftover, reason=reason,
            owner_hits=getattr(cache, "owner_hits", 0),
            remote_routes=getattr(cache, "remote_routes", 0))
        if self.recorder is not None:
            try:
                self.recorder.trigger("host_drain", force=True, sync=True,
                                      host=self.host_id, reason=reason,
                                      inflight=leftover)
            except Exception:
                pass  # the bundle is evidence, not a drain dependency
        self.close()
        self.fleet.close()
        self.drained.set()

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    # -- introspection ----------------------------------------------------

    def healthz(self) -> Dict:
        out = dict(self.fleet.health())
        with self._cv:
            out.update(host=self.host_id,
                       state=HOST_DRAINING if self.draining
                       else HOST_ALIVE,
                       inflight=self.inflight)
        return out

    def stats(self) -> Dict:
        out = dict(self.fleet.stats())
        engine = getattr(self.fleet, "engine", None)
        with self._cv:
            out.update(host=self.host_id, requests=self.requests,
                       inflight=self.inflight, draining=self.draining,
                       swept=self.swept,
                       bucket_loads=getattr(engine, "bucket_loads", 0),
                       bucket_compiles=getattr(engine, "bucket_compiles",
                                               0))
        return out

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"


def install_drain_signals(server: HostServer):
    """Port of the train loop's preemption machinery: SIGTERM/SIGINT flip
    the handler's sticky flag (no I/O in the handler — resilience.py
    discipline), and a watcher thread runs the drain outside signal
    context. Returns the PreemptionHandler (uninstall() to restore)."""
    from mine_tpu.train.resilience import PreemptionHandler

    handler = PreemptionHandler().install()

    def _watch():
        while not handler.requested and not server.drained.is_set():
            time.sleep(0.05)
        if handler.requested:
            server.drain(reason="preempt")

    threading.Thread(target=_watch, daemon=True,
                     name=f"mine-tpu-drain-watch-{server.host_id}").start()
    return handler


# a kept-alive connection the server closed under us looks like one of
# these on the NEXT request — reconnect once, transparently (a fresh
# connection failing the same way is a real failure, not staleness)
_STALE = (http.client.BadStatusLine, http.client.CannotSendRequest,
          ConnectionResetError, BrokenPipeError)
# what a bounded retry may absorb: transport errors, protocol garbage,
# truncated/mangled JSON, and a binary frame that fails the mtpu-wire1
# tripwires (same class of damage as mangled JSON) — never an application
# verdict (the error envelope arrives as a 200..5xx with valid JSON and
# is re-raised typed)
_RETRYABLE = (OSError, http.client.HTTPException, json.JSONDecodeError,
              wire.WireError)


class HostClient:
    """Stdlib HTTP client half of the transport; satisfies the RingFront
    handle protocol (render/healthz/stats/close). Connections are kept
    alive PER THREAD (`threading.local` — the RingFront pool shares one
    client across workers, and http.client connections are not
    thread-safe), with one transparent reconnect when the server closed
    a kept-alive socket under us.

    With a NetPolicy (serve.net.*) the client is hardened: split
    connect/read timeouts, `retries` extra attempts with jittered
    exponential backoff, a per-host CircuitBreaker consulted before and
    fed after every wire attempt, and the request's remaining deadline
    budget sent as `X-Mtpu-Deadline-Left-Ms` (expired budget raises
    DeadlineExceeded CLIENT-side, without a wire attempt). Policy-off
    keeps the legacy single-attempt, single-timeout behavior.

    With a WirePolicy whose format is "binary" (serve.wire.*) the client
    NEGOTIATES: the first render checks whether the peer ever advertised
    `X-Mtpu-Wire` (one /healthz round if no response has been seen yet)
    and speaks mtpu-wire1 batch frames only to a peer that did, falling
    back to this exact JSON path otherwise — counted
    `serve.wire.fallbacks`, decided once per client lifetime. Wire-off
    (the default) constructs none of it and the request path is
    byte-identical to PR 19 (test-pinned).

    `net_src`/`net_name` tag this client's edge in the faults.py
    partition matrix ("src>dst") so tests sever individual links."""

    def __init__(self, address: str, timeout_s: float = 60.0,
                 policy: Optional[NetPolicy] = None, net_src: str = "front",
                 net_name: str = "",
                 wire_policy: Optional["wire.WirePolicy"] = None):
        host, port = address.rsplit(":", 1)
        self.host = host
        self.port = int(port)
        self.address = address
        self.timeout_s = float(timeout_s)
        self.policy = policy if (policy is not None
                                 and policy.enabled) else None
        self.breaker: Optional[CircuitBreaker] = None
        if self.policy is not None:
            self.breaker = CircuitBreaker(address,
                                          self.policy.breaker_threshold,
                                          self.policy.breaker_reset_s)
        self.net_src = str(net_src)
        self.net_name = str(net_name) or address
        self._local = threading.local()
        self.reconnects = 0  # stale keep-alive sockets replaced
        self.retries = 0     # policy retry attempts actually taken
        # payload bytes over this client's link, BOTH formats — the bench
        # derives bytes/view from deltas, so the JSON arm is measurable
        self.bytes_tx = 0
        self.bytes_rx = 0
        self.wire_policy = wire_policy if (wire_policy is not None
                                           and wire_policy.binary) else None
        self._wire_ok: Optional[bool] = None  # None = not yet negotiated
        self._server_wire = False  # peer advertised X-Mtpu-Wire
        self._neg_lock = ordered_lock("serve.wire.negotiate") \
            if self.wire_policy is not None else None

    # -- connection management (per thread) -------------------------------

    def _conn(self) -> "http.client.HTTPConnection":
        conn = getattr(self._local, "conn", None)
        if conn is None:
            timeout = (self.policy.connect_timeout_s if self.policy
                       else self.timeout_s)
            conn = http.client.HTTPConnection(self.host, self.port,
                                              timeout=timeout)
            self._local.conn = conn
        return conn

    def _drop_conn(self) -> None:
        conn = getattr(self._local, "conn", None)
        self._local.conn = None
        if conn is not None:
            try:
                conn.close()
            except Exception:
                pass

    def _wire(self, method: str, path: str, payload, headers):
        """One HTTP round over this thread's kept-alive connection.
        Returns (status, content-type, raw bytes) — decoding is the
        _decode_body seam's job, so the truncation fault can hand a CUT
        binary frame up to the mtpu-wire1 tripwires (proving the
        rejection path) while the JSON path keeps raising IncompleteRead
        exactly as PR 19 pinned."""
        conn = self._conn()
        if conn.sock is None:
            conn.connect()  # under connect_timeout_s
            if self.policy is not None:
                conn.sock.settimeout(self.policy.read_timeout_s)
        conn.request(method, path, body=payload, headers=headers)
        self.bytes_tx += len(payload) if payload else 0
        resp = conn.getresponse()
        data = resp.read()
        self.bytes_rx += len(data)
        if resp.getheader(wire.WIRE_HEADER) == wire.WIRE_PROTO:
            self._server_wire = True  # capability capture (benign race)
        ctype = (resp.getheader("Content-Type") or "").split(";")[0].strip()
        if faults.net_truncate():
            self._drop_conn()
            if ctype == wire.CTYPE_BINARY:
                data = data[:len(data) // 2]  # decoder must reject it
            else:
                raise http.client.IncompleteRead(data[:len(data) // 2])
        return resp.status, ctype, data

    def _attempt(self, method: str, path: str, payload, headers):
        """One logical attempt: the fault seam, the wire, and at most one
        transparent reconnect when a REUSED connection turned out stale.
        A fresh connection's failure always propagates — retrying it is
        the retry loop's (counted) job, not this layer's."""
        faults.net_request(self.net_src, self.net_name)
        conn = getattr(self._local, "conn", None)
        reused = conn is not None and conn.sock is not None
        try:
            return self._wire(method, path, payload, headers)
        except _STALE:
            self._drop_conn()
            if not reused:
                raise
            self.reconnects += 1
            telemetry.counter("serve.net.reconnects").inc()
            try:
                return self._wire(method, path, payload, headers)
            except Exception:
                self._drop_conn()
                raise
        except Exception:
            self._drop_conn()
            raise

    # -- request path -----------------------------------------------------

    @staticmethod
    def _encode_body(body):
        """THE request-framing seam (satellite: negotiation in one
        place): dict bodies frame as the PR-19 JSON bytes; a pre-framed
        mtpu-wire1 payload (bytes) passes through with the binary
        Content-Type. Both render paths and every control endpoint
        funnel through here."""
        if body is None:
            return None, wire.CTYPE_JSON
        if isinstance(body, (bytes, bytearray)):
            return bytes(body), wire.CTYPE_BINARY
        return json.dumps(body).encode(), wire.CTYPE_JSON

    @staticmethod
    def _decode_body(ctype: str, data: bytes):
        """The response half of the seam: binary frames decode through
        the mtpu-wire1 tripwires (WireError -> retried), everything else
        parses as JSON (json.JSONDecodeError -> retried)."""
        if ctype == wire.CTYPE_BINARY:
            return wire.decode_render_response(data)
        return json.loads(data or b"{}")

    def _request(self, method: str, path: str,
                 body=None,
                 deadline_ms: Optional[float] = None,
                 retry: bool = True):
        payload, ctype = self._encode_body(body)
        headers = {"Content-Type": ctype}
        pol = self.policy
        attempts = 1 + (pol.retries if (pol is not None and retry) else 0)
        t0 = time.monotonic()
        for attempt in range(attempts):
            if (pol is not None and deadline_ms is not None
                    and deadline_ms > 0):
                left = float(deadline_ms) - (time.monotonic() - t0) * 1e3
                if left <= 0:
                    telemetry.counter("serve.net.deadline_expired").inc()
                    raise DeadlineExceeded(
                        f"{self.address}: {deadline_ms:.0f}ms budget "
                        f"spent client-side after {attempt} attempt(s)")
                headers[DEADLINE_HEADER] = f"{left:.1f}"
            if self.breaker is not None and not self.breaker.allow():
                raise BreakerOpen(f"{self.address}: circuit open")
            try:
                status, rctype, data = self._attempt(method, path,
                                                     payload, headers)
                obj = self._decode_body(rctype, data)
            except _RETRYABLE as e:
                if self.breaker is not None:
                    self.breaker.record(False)
                if isinstance(e, TimeoutError):
                    # socket.timeout IS TimeoutError on py3.10+
                    telemetry.counter("serve.net.timeouts").inc()
                elif isinstance(e, ConnectionRefusedError):
                    telemetry.counter("serve.net.refused").inc()
                if attempt + 1 >= attempts:
                    raise
                self.retries += 1
                telemetry.counter("serve.net.retries").inc()
                time.sleep(pol.backoff_ms / 1e3 * (2 ** attempt)
                           * (0.5 + random.random()))
                continue
            if self.breaker is not None:
                self.breaker.record(True)
            return status, obj
        raise RuntimeError("unreachable")  # loop always returns/raises

    def _negotiate(self) -> bool:
        """Once per client lifetime: does the peer speak mtpu-wire1? The
        advertisement header rides EVERY wire-enabled response, so any
        prior round already answered; otherwise spend one /healthz. A
        silent (JSON-only) peer or a dead probe pins the fallback —
        binary framing AND the front's coalescer stay off for this link,
        counted `serve.wire.fallbacks`."""
        with self._neg_lock:
            if self._wire_ok is not None:
                return self._wire_ok
        if not self._server_wire:
            try:
                self._request("GET", "/healthz", retry=False)
            except Exception:
                pass
        ok = self._server_wire
        with self._neg_lock:
            if self._wire_ok is None:
                self._wire_ok = ok
                if not ok:
                    telemetry.counter("serve.wire.fallbacks").inc()
        return self._wire_ok

    def wire_active(self) -> bool:
        """True when this link negotiated binary framing (the RingFront
        consults this before arming the owner-coalescer for a handle)."""
        return self.wire_policy is not None and self._negotiate()

    def render(self, image_id, pose, tier=None, deadline_ms=None,
               image=None):
        if self.wire_policy is not None and self._negotiate():
            env = self.render_batch(
                [{"image_id": image_id, "pose": pose, "tier": tier,
                  "deadline_ms": deadline_ms, "image": image}],
                deadline_ms=deadline_ms)[0]
            if env.get("ok"):
                return env["rgb"], env["depth"]
            exc = _KIND_RAISE.get(env.get("kind", ""), RuntimeError)
            raise exc(f"{self.address}: {env.get('error', '')}")
        return self._render_json(image_id, pose, tier, deadline_ms, image)

    def _render_json(self, image_id, pose, tier, deadline_ms, image):
        """The PR-19 wire, byte-identical (framed by wire.py's pinned
        JSON builders)."""
        body = wire.json_render_body(
            {"image_id": image_id, "pose": pose, "tier": tier,
             "deadline_ms": deadline_ms, "image": image})
        status, obj = self._request("POST", "/render", body,
                                    deadline_ms=deadline_ms)
        if status == 200 and obj.get("ok"):
            env = wire.json_render_result(obj)
            return env["rgb"], env["depth"]
        kind = obj.get("kind", "")
        exc = _KIND_RAISE.get(kind, RuntimeError)
        raise exc(f"{self.address}: {obj.get('error', f'HTTP {status}')}")

    def render_batch(self, reqs: List[Dict],
                     deadline_ms: Optional[float] = None) -> List[Dict]:
        """N render requests, ONE negotiated mtpu-wire1 exchange; returns
        one envelope per request IN REQUEST ORDER ({"ok": True, "rgb",
        "depth"} numpy, or {"ok": False, "kind", "error"}). Against a
        peer that never advertised, degrades to N sequential JSON rounds
        — same envelopes, PR-19 bytes."""
        if not (self.wire_policy is not None and self._negotiate()):
            out = []
            for r in reqs:
                try:
                    rgb, depth = self._render_json(
                        r["image_id"], r["pose"], r.get("tier"),
                        r.get("deadline_ms"), r.get("image"))
                    out.append({"ok": True, "rgb": rgb, "depth": depth})
                except Exception as e:
                    out.append({"ok": False, "kind": type(e).__name__,
                                "error": str(e)})
            return out
        t0 = time.monotonic()
        payload = wire.encode_render_request(
            reqs, codec=self.wire_policy.codec)
        telemetry.histogram("serve.wire.encode_ms").record(
            (time.monotonic() - t0) * 1e3)
        status, obj = self._request("POST", "/render", payload,
                                    deadline_ms=deadline_ms)
        if isinstance(obj, list):
            if len(obj) != len(reqs):
                # a valid frame with the wrong arity is a server bug,
                # not wire damage — surface it, don't retry it
                raise RuntimeError(
                    f"{self.address}: batch response carries {len(obj)} "
                    f"envelope(s) for {len(reqs)} request(s)")
            return obj
        # a JSON envelope to a binary frame is a BATCH-level verdict
        # (hostile-frame 400, draining 503, ...): re-raise typed
        kind = obj.get("kind", "")
        exc = _KIND_RAISE.get(kind, RuntimeError)
        raise exc(f"{self.address}: {obj.get('error', f'HTTP {status}')}")

    def probe(self) -> Dict:
        """One /healthz round-trip that BYPASSES allow(): the front's
        heartbeat prober IS the half-open admission — its verdict feeds
        the breaker either way, so an open circuit heals from probes
        without spending a caller's request on it."""
        headers = {"Content-Type": wire.CTYPE_JSON}
        try:
            _, rctype, data = self._attempt("GET", "/healthz", None,
                                            headers)
            obj = self._decode_body(rctype, data)
        except Exception:
            if self.breaker is not None:
                self.breaker.record(False)
            raise
        if self.breaker is not None:
            self.breaker.record(True)
        return obj

    def breaker_snapshot(self) -> Optional[Dict]:
        return self.breaker.snapshot() if self.breaker is not None \
            else None

    def healthz(self) -> Dict:
        return self._request("GET", "/healthz")[1]

    def stats(self) -> Dict:
        return self._request("GET", "/stats")[1]

    def drain(self) -> Dict:
        return self._request("POST", "/drain", {}, retry=False)[1]

    def close(self) -> None:
        # drops the CALLING thread's kept-alive socket; other threads'
        # are closed by GC when the client goes away (daemon pool)
        self._drop_conn()


def _entries_counts(limit: int):
    """Every pow2 entries bucket the batcher can form (<= max_requests):
    the warmup set a host must cover so a concurrent flood — which
    coalesces distinct cache entries into R>1 dispatch batches — never
    triggers a live compile after a zero-compile join."""
    out, b = [], 1
    while b <= limit:
        out.append(b)
        b *= 2
    return out


def _build_fleet(args, encode_fn, recorder=None):
    from mine_tpu.serve import ServeFleet

    return ServeFleet(
        cache_shards=args.cache_shards, max_requests=args.max_requests,
        max_wait_ms=2.0, max_bucket=args.max_bucket, encode_fn=encode_fn,
        slo_objective_ms=args.slo_objective_ms, ops_port=None,
        encode_retries=3, encode_backoff_ms=5.0,
        admission_enabled=args.admission,
        admission_burn_max=0.0, admission_queue_high=args.queue_high,
        admission_inflight_high=0, aot_store_dir=args.aot_store,
        recorder=recorder)


def main(argv=None) -> int:
    """Subprocess host entrypoint (see module docstring). Every line of
    stdout is "key=value ..."-parseable; the spawner reads the `ready=1`
    line for the bound port and the zero-compile-join evidence."""
    import argparse
    import os
    import tempfile

    ap = argparse.ArgumentParser(
        description="mine-tpu serving ring host (stdlib HTTP/JSON)")
    ap.add_argument("--host-id", type=str, required=True)
    ap.add_argument("--port", type=int, default=0,
                    help="0 = ephemeral; the bound port is printed")
    ap.add_argument("--cache-shards", type=int, default=2)
    ap.add_argument("--max-bucket", type=int, default=2)
    ap.add_argument("--max-requests", type=int, default=8)
    ap.add_argument("--slo-objective-ms", type=float, default=0.0)
    ap.add_argument("--admission", action="store_true",
                    help="enable the local admission ladder")
    ap.add_argument("--queue-high", type=int, default=64)
    ap.add_argument("--aot-store", type=str, default="",
                    help="AOT executable store directory")
    ap.add_argument("--aot-artifact", type=str, default="",
                    help="packed artifact (aot_warmstore.py --pack); "
                         "unpacked to a fresh store dir before boot")
    ap.add_argument("--warm-key", type=str, default="",
                    help="image id to put+warmup at boot — the warmup is "
                         "what records the AOT loads/compiles evidence")
    ap.add_argument("--warm-seed", type=int, default=0,
                    help="synthetic image seed for --warm-key")
    ap.add_argument("--drain-timeout-s", type=float, default=30.0)
    ap.add_argument("--wire", choices=list(wire.WIRE_FORMATS),
                    default="json",
                    help="binary advertises mtpu-wire1 + accepts batch "
                         "frames on /render (serve.wire.format)")
    ap.add_argument("--incidents-dir", type=str, default="",
                    help="arm a flight recorder; drains dump a bundle")
    ap.add_argument("--build-artifact", type=str, default="",
                    help="builder mode: boot the same fleet, warm every "
                         "bucket, pack the store to this path, exit — "
                         "the artifact hosts then boot from is guaranteed "
                         "program-key-compatible")
    args = ap.parse_args(argv)

    from mine_tpu.serve import aot as serve_aot

    if args.build_artifact:
        store_dir = args.aot_store or tempfile.mkdtemp(
            prefix=f"host_{args.host_id}_build_")
        args.aot_store = store_dir
        fleet = _build_fleet(args, synthetic_encode_fn)
        img = np.full((SYN_HW, SYN_HW, 3), float(args.warm_seed),
                      np.float32)
        key = args.warm_key or "builder"
        fleet.engine.put(key, *synthetic_encode_fn(img))
        fleet.warmup(key,
                     entries_counts=_entries_counts(args.max_requests))
        compiles = fleet.engine.bucket_compiles
        loads = fleet.engine.bucket_loads
        fleet.close()
        manifest = serve_aot.pack_store(store_dir, args.build_artifact)
        print(f"host={args.host_id} built=1 compiles={compiles} "
              f"loads={loads} packed={manifest['artifacts']} "
              f"artifact={args.build_artifact}", flush=True)
        return 0

    if args.aot_artifact:
        # the packed artifact is the deployable unit: unpack to a private
        # store dir so concurrent hosts never share write paths
        store_dir = tempfile.mkdtemp(prefix=f"host_{args.host_id}_aot_")
        serve_aot.unpack_store(args.aot_artifact, store_dir)
        args.aot_store = store_dir
        print(f"host={args.host_id} unpacked_store={store_dir}",
              flush=True)

    recorder = None
    if args.incidents_dir:
        from mine_tpu.telemetry import recorder as trecorder

        recorder = trecorder.configure(
            args.incidents_dir, debounce_s=1.0, keep=8,
            config={"host": args.host_id})

    fleet = _build_fleet(args, synthetic_encode_fn, recorder=recorder)
    loads = compiles = 0
    if args.warm_key:
        img = np.full((SYN_HW, SYN_HW, 3), float(args.warm_seed),
                      np.float32)
        fleet.engine.put(args.warm_key, *synthetic_encode_fn(img))
        fleet.warmup(args.warm_key,
                     entries_counts=_entries_counts(args.max_requests))
        loads = fleet.engine.bucket_loads
        compiles = fleet.engine.bucket_compiles

    wire_policy = (wire.WirePolicy(format="binary")
                   if args.wire == "binary" else None)
    server = HostServer(fleet, args.host_id, port=args.port,
                        drain_timeout_s=args.drain_timeout_s,
                        recorder=recorder, wire_policy=wire_policy).start()
    handler = install_drain_signals(server)
    telemetry.emit("serve.host_join", host=args.host_id, hosts=1,
                   aot_loads=loads, aot_compiles=compiles)
    print(f"host={args.host_id} port={server.port} ready=1 "
          f"aot_loads={loads} aot_compiles={compiles} pid={os.getpid()}",
          flush=True)

    server.drained.wait()
    handler.uninstall()
    if recorder is not None:
        from mine_tpu.telemetry import recorder as trecorder

        trecorder.release(recorder)
    print(f"host={args.host_id} drained=1", flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
