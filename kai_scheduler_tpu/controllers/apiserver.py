"""HTTP API server: the real-cluster communication substrate.

Serves the ``InMemoryKubeAPI`` object store over a Kubernetes-style REST +
watch protocol so that controllers in OTHER processes (or on other hosts)
can run the exact same code paths they use in-process.  This is the analog
of the reference fleet's dependence on a live apiserver — informers and
clientsets in ``/root/reference/pkg/apis/client/``, watch-config in
``pkg/scheduler/scheduler.go:141-147`` — rebuilt as a compact HTTP server
over the typed store instead of etcd.

Daemon-scale transport (DESIGN §12).  The server is built like a server,
not a thread-per-connection toy:

- **Pooled dispatch**: one selector-loop dispatcher thread multiplexes
  every keep-alive connection; a readable connection is handed to a
  BOUNDED worker pool (one request per dispatch, then back to the
  selector).  Saturation answers ``429 Too Many Requests`` instead of
  spawning threads without bound (``apiserver_pool_saturated_total``);
  long-lived watch streams detach onto dedicated streamer threads so
  they never occupy pool workers.  Clients must not pipeline requests
  on one connection (ours never do): the dispatcher wakes on socket
  readability, not on buffered leftovers.
- **Preserialized frames**: every mutation's object is JSON-encoded
  exactly ONCE, at event-append time; watch streams fan the cached
  chunk bytes out verbatim (``watch_frame_cache_hits_total`` vs
  ``_misses_total`` — the encode counter), and list/get responses are
  assembled from the same per-(object, resourceVersion) byte cache
  instead of re-running ``json.dumps`` per request.
- **Pagination + field selectors**: ``GET /apis/{kind}?limit=N&
  continue=TOK&fieldSelector=spec.nodeName=n1,status.phase!=Running``
  pages the name-ordered listing with an opaque cursor token; a token
  minted before the event ring compacted past it (or by a previous
  server boot) answers ``410 Gone`` and the client transparently
  re-lists — the K8s expired-continue contract.
- **Bulk mutation endpoints**: ``POST /bulk/create`` (the bind-wave
  batch; ``supersede`` replaces an existing object on conflict) and
  ``POST /bulk/patch`` (batched status/spec merge patches) apply a
  whole wave under ONE lock acquisition and return per-item outcomes —
  one fenced or conflicting item fails that item only.  Fencing is
  checked per item; ``X-Kai-Epoch``/``X-Kai-Fence`` headers (or
  per-item overrides in the body) keep PR 2's semantics unchanged.

Protocol (JSON bodies everywhere):

  POST   /apis/{kind}                      create
  GET    /apis/{kind}?namespace=&labelSelector=&fieldSelector=&limit=&continue=
  GET    /apis/{kind}/{namespace}/{name}   get
  PUT    /apis/{kind}/{namespace}/{name}   update (replace)
  PATCH  /apis/{kind}/{namespace}/{name}   strategic-merge patch
  DELETE /apis/{kind}/{namespace}/{name}   delete
  POST   /bulk/create                      batched create (bind waves)
  POST   /bulk/patch                       batched merge patch
  GET    /watch?since={seq}                chunked stream of events
  GET    /relist                           atomic snapshot + seq
  GET    /healthz
  GET    /metrics                          Prometheus text — the
                                           server-end wire-observatory
                                           counters live here in the
                                           split-process regime
  GET    /debug/spans?since={id}           server-side request/fanout
                                           span records after cursor
                                           (the distributed-trace
                                           graft pull)

Every mutation response carries ``X-Kai-Seq``: the event-log sequence
AFTER the write's events were appended.  A client that waits for its
watch cursor to reach that seq has read its own writes — the cheap
incremental-state barrier the fleet cycle uses instead of re-listing.

The watch stream emits one JSON object per line:
``{"seq": N, "type": "ADDED|MODIFIED|DELETED", "object": {...}}``
plus periodic ``{"type": "HEARTBEAT", "seq": N}`` keep-alives.  ``seq`` is
a server-side monotonic event sequence (the resourceVersion analog for
watch resumption): a client reconnecting with ``since=N`` replays every
event after N from the ring buffer, exactly like an informer re-list.

Watch-gap contract: a ``since`` outside the ring's retained window —
older than the horizon (events evicted) or NEWER than the head (the
server restarted and its sequence reset) — gets one explicit
``{"type": "GONE", "code": 410, "seq": <head>}`` line and the stream
closes.  The server never silently replays a truncated history; the
client must re-list (``GET /relist`` returns an atomic
``{"seq", "items"}`` snapshot), diff its store, and resume from the
returned head — exactly K8s' 410 Gone + informer re-list protocol.

Errors map to status codes: 404 NotFound, 409 Conflict, 412 Fenced (a
deposed leader's write; epoch travels in the ``X-Kai-Epoch`` /
``X-Kai-Fence`` request headers), 410 Gone (expired continue token),
429 pool saturation — the HTTP client (httpclient.py) converts them
back into the same exceptions ``InMemoryKubeAPI`` raises, so callers
cannot tell the substrates apart.
"""

from __future__ import annotations

import base64
import copy
import io
import itertools
import json
import os
import queue
import selectors
import socket
import threading
import time
import uuid
from collections import deque
from http.server import BaseHTTPRequestHandler
from urllib.parse import parse_qs, urlparse

from ..utils import wireobs
from ..utils.deviceguard import control_fault
from ..utils.logging import ScopedLogger
from ..utils.metrics import METRICS
from ..utils.tracing import SPAN_HEADER, TRACE_HEADER
from .kubeapi import (Conflict, Fenced, InMemoryKubeAPI, NotFound,
                      field_match, obj_key, parse_field_selector)

log = ScopedLogger("apiserver")

EVENT_LOG_CAPACITY = 100_000
HEARTBEAT_SECONDS = 1.0
POOL_SIZE = 8
POOL_BACKLOG = 64
MAX_WATCH_STREAMS = 64
REQUEST_TIMEOUT_S = 30.0
DEFAULT_PAGE_LIMIT = 0  # 0 = unpaginated unless the client asks


def _dumps(payload) -> bytes:
    # Compact separators: the wire ships no decorative whitespace.
    return json.dumps(payload, separators=(",", ":")).encode()


def _chunk(line: bytes) -> bytes:
    """HTTP/1.1 chunked-transfer framing for one ndjson line."""
    return f"{len(line):x}\r\n".encode() + line + b"\r\n"


def _corrupt_chunk(chunk: bytes) -> bytes:
    """wire-corrupt fault: overwrite a run of payload bytes with 0xFE
    (not valid UTF-8, not valid JSON) while PRESERVING the chunk's
    length framing — the transfer coding stays intact, so the lie
    reaches the client's JSON layer, the worst place to be lied to."""
    head = chunk.index(b"\r\n") + 2
    body = bytearray(chunk)
    mid = head + max(1, (len(chunk) - head - 3) // 3)
    for i in range(mid, min(len(chunk) - 3, mid + 8)):
        body[i] = 0xFE
    return bytes(body)


class _FrameCache:
    """Preserialized object frames keyed (kind, ns, name) -> (rv, bytes).

    One entry per live object, refreshed at event-append time (every
    mutation emits an event, so the cache tracks the store); list/get
    responses are concatenations of these frames.  Guarded by its own
    lock: appends may run on any mutating thread (in-process embedders
    drain the store outside the HTTP server's lock)."""

    def __init__(self):
        self._lock = threading.Lock()
        # Multi-writer BY DESIGN (mutating threads + pool workers), every
        # access under _lock — no single-writer contract to annotate.
        self._frames: dict = {}
        # Regression lever for the fleet_budget wire gates: disabling
        # the cache makes every list/get re-encode every object per
        # request, which the max-encodes-per-cycle ceiling and the
        # frame-cache byte-hit ratio must catch loudly.
        self._disabled = os.environ.get("KAI_WIRE_NO_FRAME_CACHE",
                                        "") not in ("", "0")

    def put(self, key: tuple, rv, data: bytes) -> None:
        with self._lock:
            self._frames[key] = (rv, data)

    def drop(self, key: tuple) -> None:
        with self._lock:
            self._frames.pop(key, None)

    def get(self, key: tuple, rv) -> bytes | None:
        with self._lock:
            entry = self._frames.get(key)
        if entry is not None and entry[0] == rv:
            return entry[1]
        return None

    def serialize(self, obj: dict) -> bytes:
        """Frame bytes for ``obj`` — cached when its resourceVersion
        matches, encoded (and counted as a miss) otherwise.  Callers
        hold whatever lock makes ``obj`` stable (the server lock)."""
        key = obj_key(obj)
        rv = obj.get("metadata", {}).get("resourceVersion")
        data = (self.get(key, rv)
                if rv is not None and not self._disabled else None)
        if data is not None:
            METRICS.inc("watch_frame_cache_hits_total")
            wireobs.count_frame_bytes("cache", len(data))
            return data
        METRICS.inc("watch_frame_cache_misses_total")
        # Serve-path encodes separately from the compulsory one-per-
        # mutation append encode: with a warm cache this stays near
        # zero, so the wire budget can pin it structurally.
        METRICS.inc("frame_cache_serve_encodes_total")
        data = _dumps(obj)
        wireobs.count_frame_bytes("encode", len(data))
        if rv is not None and not self._disabled:
            self.put(key, rv, data)
        return data


class EventLog:
    """Bounded, sequenced event history for watch resumption.

    Entries are ``(seq, event_type, obj, chunk)`` where ``chunk`` is the
    PRESERIALIZED chunked-transfer frame for the watch line: the object
    is JSON-encoded exactly once, here, and every watcher streams the
    same bytes verbatim."""

    def __init__(self, capacity: int = EVENT_LOG_CAPACITY,
                 frames: _FrameCache | None = None):
        self._events: deque = deque(maxlen=capacity)
        self._seq = 0
        self.cond = threading.Condition()
        self.frames = frames if frames is not None else _FrameCache()

    def append(self, event_type: str, obj: dict) -> None:
        # Deep copy at emit time: the store's live dict keeps mutating
        # under later patches, and the streamer writes outside the
        # server lock — a snapshot keeps replayed history faithful and
        # the cached frame bytes race-free.
        obj = copy.deepcopy(obj)
        try:
            key = obj_key(obj)
        except KeyError:
            key = None  # degenerate manifest: no response-frame entry
        rv = obj.get("metadata", {}).get("resourceVersion")
        # ONE encode per mutation: the object bytes feed both the watch
        # frame below and the list/get response cache.
        METRICS.inc("watch_frame_cache_misses_total")
        obj_bytes = _dumps(obj)
        wireobs.count_frame_bytes("encode", len(obj_bytes))
        if key is not None:
            if event_type == "DELETED":
                self.frames.drop(key)
            elif rv is not None:
                self.frames.put(key, rv, obj_bytes)
        with self.cond:
            self._seq += 1
            line = (b'{"seq":' + str(self._seq).encode() +
                    b',"type":"' + event_type.encode() +
                    b'","object":' + obj_bytes + b'}\n')
            self._events.append((self._seq, event_type, obj, _chunk(line)))
            self.cond.notify_all()

    @property
    def seq(self) -> int:
        with self.cond:
            return self._seq

    def oldest(self) -> int:
        """Seq number just before the oldest retained event: a client
        resuming from anything older has lost events to ring eviction."""
        with self.cond:
            return self._seq - len(self._events)

    def since(self, seq: int) -> list:
        """Events with seq > ``seq``.  Sequences are assigned contiguously,
        so the suffix is a tail slice of the ring — O(result), not a scan
        of the whole retained history per watcher wakeup."""
        with self.cond:
            missing = self._seq - seq
            if missing <= 0:
                return []
            if missing >= len(self._events):
                return list(self._events)
            tail = list(itertools.islice(reversed(self._events), missing))
            tail.reverse()
            return tail


def _encode_continue(boot: str, seq: int, after: tuple) -> str:
    token = _dumps({"b": boot, "s": seq, "k": list(after)})
    return base64.urlsafe_b64encode(token).decode()


def _decode_continue(token: str) -> dict | None:
    try:
        out = json.loads(base64.urlsafe_b64decode(token.encode()))
        return out if isinstance(out, dict) else None
    except (ValueError, TypeError):
        return None


class KubeAPIServer:
    """Serve an InMemoryKubeAPI over HTTP with watch streaming.

    All store mutations are serialized under one lock (the apiserver is the
    consistency point, as in Kubernetes); events drain into the EventLog
    immediately after each mutation so watchers observe every transition in
    order.  Request DISPATCH is concurrent: a selector loop plus a bounded
    worker pool (see the module docstring) — the lock scopes consistency,
    not parsing or serialization.
    """

    def __init__(self, api: InMemoryKubeAPI | None = None,
                 host: str = "127.0.0.1", port: int = 0,
                 event_log_capacity: int = EVENT_LOG_CAPACITY,
                 pool_size: int = POOL_SIZE,
                 pool_backlog: int = POOL_BACKLOG,
                 max_watch_streams: int = MAX_WATCH_STREAMS):
        self.api = api or InMemoryKubeAPI()
        self.frames = _FrameCache()
        self.log = EventLog(capacity=event_log_capacity, frames=self.frames)
        self.lock = threading.RLock()
        self.max_watch_streams = max_watch_streams
        # Per-boot identity: seq numbers are only comparable within ONE
        # server lifetime.  Clients echo the boot id on resume; a
        # mismatch is a restart and forces GONE+relist even when the new
        # log's head seq happens to have caught up past the client's old
        # cursor (ordering alone cannot detect that case).
        self.boot_id = uuid.uuid4().hex[:12]
        self._log_appender = lambda et, obj: self.log.append(et, obj)
        self.api.watch_any(self._log_appender)
        # Objects created BEFORE this server attached never emitted an
        # event through our log: prime their response frames so the
        # first lists stream cached bytes too.
        with self.lock:
            for obj in list(self.api.objects.values()):
                rv = obj.get("metadata", {}).get("resourceVersion")
                if rv is not None:
                    self.frames.put(obj_key(obj), rv, _dumps(obj))
        # Set on stop(): active watch-stream threads (which outlive the
        # pool) must terminate their connections, or an in-process
        # "restart" leaves clients reading heartbeats from a zombie
        # streamer forever instead of reconnecting.
        self._closing = threading.Event()
        # Live watch streamer SLOTS (bounded by max_watch_streams).
        # The smallest-free slot index doubles as the watcher's metric
        # label (`stream`) — bounded cardinality by construction, never
        # a client identity.
        self._watch_slots: set = set()
        self._watch_lock = threading.Lock()
        # Wire observatory (PR 19): completed server-side span records
        # (request phases + watch fanout bursts), bounded ring, served
        # at GET /debug/spans?since= and grafted into the scheduler's
        # cycle traces by Tracer.graft_remote_spans.
        self.spans = wireobs.SpanRing()
        # Wire-fault bookkeeping (KAI_FAULT_INJECT wire-* modes): one
        # deterministic counter per mode, server-wide — "first n" and
        # "every nth" semantics must hold across connections and pool
        # workers, so per-stream locals are not enough.
        self._wire_lock = threading.Lock()
        self._wire_counts: dict = {}
        self.httpd = _PooledHTTPServer((host, port), self,
                                       pool_size=pool_size,
                                       backlog=pool_backlog)
        self._thread: threading.Thread | None = None

    @property
    def port(self) -> int:
        return self.httpd.server_port

    @property
    def url(self) -> str:
        host, port = self.httpd.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "KubeAPIServer":
        self.httpd.start()
        return self

    def stop(self) -> None:
        self._closing.set()
        # Stop feeding (and deep-copying into) a log nobody will read —
        # an in-process restart otherwise leaks one zombie subscriber
        # per server generation.
        unwatch = getattr(self.api, "unwatch_any", None)
        if unwatch is not None:
            unwatch(self._log_appender)
        with self.log.cond:
            self.log.cond.notify_all()  # wake streams so they exit now
        self.httpd.shutdown()

    # -- handlers (store access under self.lock) -----------------------------
    def handle(self, method: str, kind: str, namespace: str | None,
               name: str | None, query: dict, body: dict | None,
               epoch: int | None = None, fence: str | None = None):
        """Single-object CRUD; returns (code, payload_dict, seq)."""
        api = self.api
        with self.lock:
            try:
                if method == "POST":
                    out = api.create(body, epoch=epoch, fence=fence)
                elif method == "GET":
                    out = api.get(kind, name, namespace)
                elif method == "PUT":
                    out = api.update(body, epoch=epoch, fence=fence)
                elif method == "PATCH":
                    out = api.patch(kind, name, body, namespace,
                                    epoch=epoch, fence=fence)
                elif method == "DELETE":
                    api.delete(kind, name, namespace,
                               epoch=epoch, fence=fence)
                    out = {}
                else:
                    return 405, {"error": f"bad method {method}"}, None
            except NotFound as e:
                return 404, {"error": str(e)}, None
            except Conflict as e:
                return 409, {"error": str(e)}, None
            except Fenced as e:
                return 412, {"error": str(e), "fenced": True}, None
            # Push events to the log right away so watch streams are live
            # even when no in-process controller calls drain().
            api.drain()
            seq = self.log.seq if method != "GET" else None
        return 200, out, seq

    def handle_list(self, kind: str, query: dict):
        """Paginated, selector-filtered list.  Returns
        (code, body_bytes, continue_token_or_None).

        The listing walks the live store in (name, namespace) order; a
        ``continue`` token records the cursor plus the event seq at
        issuance.  A token from another boot, or older than the event
        ring's horizon (the churn between then and now is unknowable),
        answers 410 Gone — the expired-continue contract."""
        namespace = query.get("namespace")
        label_sel = _parse_selector(query.get("labelSelector"))
        field_sel = parse_field_selector(query.get("fieldSelector"))
        try:
            limit = int(query.get("limit", DEFAULT_PAGE_LIMIT))
        except ValueError:
            limit = DEFAULT_PAGE_LIMIT
        token = query.get("continue")
        after = None
        METRICS.inc("apiserver_list_requests_total", kind=kind)
        if not (label_sel or field_sel or namespace or limit):
            # The regression the fleet gate hunts: a client shipping a
            # whole kind, unbounded and unfiltered, per request.
            METRICS.inc("apiserver_whole_kind_lists_total", kind=kind)
        if token:
            tok = _decode_continue(token)
            stale = (tok is None or tok.get("b") != self.boot_id
                     or int(tok.get("s", 0)) < self.log.oldest())
            if stale:
                METRICS.inc("apiserver_list_continue_gone_total")
                return 410, _dumps({"error": "continue token expired "
                                             "(compacted or rebooted)",
                                    "gone": True}), None
            after = tuple(tok.get("k") or ())
        with self.lock:
            rows = []
            for (k, ns, nm), obj in self.api.objects.items():
                if k != kind:
                    continue
                if namespace is not None and ns != namespace:
                    continue
                rows.append(((nm, ns), obj))
            rows.sort(key=lambda row: row[0])
            frames = []
            next_token = None
            seq_now = self.log.seq
            for cursor, obj in rows:
                if after is not None and cursor <= after:
                    continue
                if label_sel:
                    labels = obj.get("metadata", {}).get("labels", {})
                    if any(labels.get(lk) != lv
                           for lk, lv in label_sel.items()):
                        continue
                if field_sel is not None \
                        and not field_match(obj, field_sel):
                    continue
                frames.append(self.frames.serialize(obj))
                if limit and len(frames) >= limit:
                    next_token = _encode_continue(self.boot_id, seq_now,
                                                  cursor)
                    break
        METRICS.inc("apiserver_list_pages_total")
        body = bytearray(b'{"items":[')
        body += b",".join(frames)
        body += b"]"
        if next_token is not None:
            body += b',"continue":"' + next_token.encode() + b'"'
        body += b"}"
        return 200, bytes(body), next_token

    def handle_bulk(self, op: str, body: dict,
                    epoch: int | None, fence: str | None):
        """Bulk mutation: apply every item under ONE lock acquisition,
        fence-checked per item, and report per-item outcomes — one bad
        item never poisons the wave.  Returns (code, payload, seq)."""
        items = (body or {}).get("items")
        if not isinstance(items, list):
            return 400, {"error": "bulk body must carry items: [...]"}, None
        supersede = bool((body or {}).get("supersede"))
        METRICS.inc("apiserver_bulk_requests_total", op=op)
        METRICS.inc("apiserver_bulk_items_total", len(items), op=op)
        with self.lock:
            if op == "create":
                raw = self.api.create_many(items, epoch=epoch, fence=fence,
                                           supersede=supersede)
            else:
                raw = self.api.patch_many(items, epoch=epoch, fence=fence)
            self.api.drain()
            seq = self.log.seq
        outcomes = []
        for out in raw:
            if out.get("ok"):
                ok = {"ok": True, "object": out["object"]}
                if out.get("noop"):
                    ok["noop"] = True  # replayed item: fence-checked no-op
                outcomes.append(ok)
            else:
                exc = out.get("error")
                code = (404 if isinstance(exc, NotFound)
                        else 409 if isinstance(exc, Conflict)
                        else 412 if isinstance(exc, Fenced) else 500)
                outcomes.append({"ok": False, "code": code,
                                 "error": str(exc)})
        return 200, {"outcomes": outcomes}, seq

    # -- wire-fault injection (KAI_FAULT_INJECT wire-* modes) ----------------
    def wire_fault_fires(self, mode: str, default_n: int,
                         every: bool = False) -> bool:
        """Count one qualifying event for ``mode`` and report whether
        THIS one faults.  ``every=False`` = the first N events fault
        (storms); ``every=True`` = every Nth event faults (resets).
        Deterministic by construction — the same request sequence
        faults at the same points on every run, which is what lets the
        chaos matrix replay a flaking seed."""
        spec = control_fault(mode)
        if spec is None:
            return False
        try:
            n = int(spec) if spec else default_n
        except ValueError:
            n = default_n
        if n <= 0:
            return False
        with self._wire_lock:
            count = self._wire_counts.get(mode, 0) + 1
            self._wire_counts[mode] = count
        fires = (count % n == 0) if every else (count <= n)
        if fires:
            METRICS.inc("wire_faults_injected_total", mode=mode)
        return fires

    # -- anti-entropy digest -------------------------------------------------
    def digest_snapshot(self) -> dict:
        """Per-kind store digest at one event seq (``GET /digest``) —
        the server half of the anti-entropy exchange
        (utils/antientropy.py).  Atomic under the server lock (no HTTP
        mutation can land between the fold and the seq read), with the
        fold itself delegated to ``api.digest()`` so the STORE lock
        guards the hashing — in-process embedders patch objects in
        place under that lock only, and a half-merged manifest must
        never tear a hash.  The O(store) fold per call is the accepted
        cost of a periodic, per-interval exchange (fleet-budget-green
        at the 2000n/4000p shape); an incrementally maintained XOR in
        ``EventLog.append`` is the known next rung, at the price of a
        second (canonical) encode on every mutation's hot path."""
        with self.lock:
            kinds = self.api.digest()["kinds"]
            return {"seq": self.log.seq, "boot": self.boot_id,
                    "kinds": kinds}

    def relist_snapshot(self) -> dict:
        """Atomic full-store snapshot + the event seq it corresponds to —
        the client's 410-GONE recovery re-list.  Taken under the server
        lock so no event can land between the copy and the seq read: a
        client resuming its watch from the returned seq misses nothing."""
        with self.lock:
            items = [copy.deepcopy(o) for o in self.api.objects.values()]
            return {"seq": self.log.seq, "boot": self.boot_id,
                    "items": items}

    # -- watch streamer accounting ------------------------------------------
    def acquire_watch_slot(self) -> int | None:
        """Claim the smallest free streamer slot index, or None at the
        cap.  The index labels this watcher's fanout/depth metrics."""
        with self._watch_lock:
            if len(self._watch_slots) >= self.max_watch_streams:
                return None
            slot = 0
            while slot in self._watch_slots:
                slot += 1
            self._watch_slots.add(slot)
            return slot

    def release_watch_slot(self, slot: int) -> None:
        with self._watch_lock:
            self._watch_slots.discard(slot)


def selectors_select_one(sock: socket.socket, timeout: float) -> bool:
    """Readability poll on one socket (the worker linger)."""
    import select
    r, _w, _x = select.select([sock], [], [], timeout)
    return bool(r)


def _parse_selector(raw: str | None) -> dict | None:
    if not raw:
        return None
    out = {}
    for part in raw.split(","):
        if "=" in part:
            k, v = part.split("=", 1)
            out[k] = v
    return out


class _SocketWriter(io.RawIOBase):
    """Unbuffered socket writer with FULL-write semantics: ``write``
    sends the whole buffer (``sendall``), unlike the raw ``SocketIO``
    ``socket.makefile('wb', 0)`` returns, whose single ``send`` may
    write PARTIALLY and silently drop the tail of a large response
    (socketserver's private ``_SocketWriter`` exists for exactly this
    reason)."""

    def __init__(self, sock: socket.socket):
        self._sock = sock

    def writable(self) -> bool:
        return True

    def write(self, b) -> int:
        self._sock.sendall(b)
        with memoryview(b) as view:
            return view.nbytes

    def fileno(self) -> int:
        return self._sock.fileno()


class _Conn:
    """One accepted connection: socket + buffered reader + raw writer +
    its (reusable) request handler."""

    __slots__ = ("sock", "addr", "rfile", "wfile", "handler",
                 "enqueued_at")

    def __init__(self, sock: socket.socket, addr, server: KubeAPIServer):
        self.sock = sock
        self.addr = addr
        self.rfile = sock.makefile("rb", -1)
        # Unbuffered sendall-backed writes: response bodies are single
        # pre-assembled buffers; watch streams batch per event burst.
        self.wfile = _SocketWriter(sock)
        self.handler = _Handler(self, server)
        # Stamped by the dispatcher at queue time; the handler's
        # queue_wait phase is (dequeue - enqueue).  None when the
        # worker served this request during its linger (no queue hop).
        self.enqueued_at: float | None = None

    def close(self) -> None:
        for closer in (self.rfile.close, self.wfile.close,
                       self.sock.close):
            try:
                closer()
            except OSError:
                pass


_SATURATED_BODY = b'{"error":"server busy (worker pool saturated)"}'
_SATURATED_RESPONSE = (
    b"HTTP/1.1 429 Too Many Requests\r\n"
    b"Content-Type: application/json\r\n"
    b"Content-Length: " + str(len(_SATURATED_BODY)).encode() + b"\r\n"
    b"Retry-After: 0\r\n"
    b"Connection: close\r\n\r\n" + _SATURATED_BODY)


class _PooledHTTPServer:
    """Selector-loop dispatcher + bounded worker pool.

    The dispatcher thread owns a selector over every idle keep-alive
    connection (plus the listen socket).  A readable connection is
    unregistered and queued; a pool worker serves exactly ONE request,
    then hands the connection back to the selector.  When the queue is
    full the connection is answered 429 and closed — bounded memory and
    threads under any client load (the DEGRADATION table's pool-
    saturation row).  Watch streams detach onto dedicated threads inside
    the handler, so they occupy no pool worker."""

    def __init__(self, addr, server: KubeAPIServer,
                 pool_size: int = POOL_SIZE, backlog: int = POOL_BACKLOG):
        self.server = server
        self.pool_size = max(1, pool_size)
        self._listen = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listen.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listen.bind(addr)
        self._listen.listen(128)
        self._listen.setblocking(False)
        self.server_address = self._listen.getsockname()
        self.server_port = self.server_address[1]
        self._work: queue.Queue = queue.Queue(maxsize=max(1, backlog))
        self._requeue: deque = deque()
        self._waker_r, self._waker_w = socket.socketpair()
        self._waker_r.setblocking(False)
        self._selector = selectors.DefaultSelector()
        self._selector.register(self._listen, selectors.EVENT_READ,
                                "listen")
        self._selector.register(self._waker_r, selectors.EVENT_READ,
                                "waker")
        self._shutdown = threading.Event()
        # Every live connection, for teardown.  Guarded by _conns_lock
        # (dispatcher adds, workers remove).
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        self._threads: list = []

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> None:
        t = threading.Thread(target=self._dispatch_loop, daemon=True,
                             name="apiserver-dispatch")
        t.start()
        self._threads.append(t)
        for i in range(self.pool_size):
            w = threading.Thread(target=self._worker_loop, daemon=True,
                                 name=f"apiserver-worker-{i}")
            w.start()
            self._threads.append(w)

    def serve_forever(self) -> None:
        """Foreground entrypoint (``python -m ...apiserver``)."""
        self.start()
        self._shutdown.wait()

    def shutdown(self) -> None:
        self._shutdown.set()
        try:
            self._waker_w.send(b"\0")
        except OSError:
            pass
        for _ in range(self.pool_size):
            try:
                self._work.put_nowait(None)
            except queue.Full:
                break
        for t in self._threads:
            t.join(timeout=2.0)
        with self._conns_lock:
            conns, self._conns = set(self._conns), set()
        for conn in conns:
            conn.close()
        for sock in (self._listen, self._waker_r, self._waker_w):
            try:
                sock.close()
            except OSError:
                pass

    def server_close(self) -> None:  # http.server compat
        pass

    # -- dispatcher ----------------------------------------------------------
    def _register(self, conn: _Conn) -> None:
        """Hand a connection back to the selector (worker thread) —
        the waker nudges the dispatcher to pick it up."""
        self._requeue.append(conn)
        try:
            self._waker_w.send(b"\0")
        except OSError:
            pass

    def _dispatch_loop(self) -> None:
        while not self._shutdown.is_set():
            try:
                events = self._selector.select(timeout=0.5)
            except OSError:
                break
            while self._requeue:
                conn = self._requeue.popleft()
                try:
                    self._selector.register(conn.sock,
                                            selectors.EVENT_READ, conn)
                except (KeyError, ValueError, OSError):
                    self._drop(conn)
            for key, _mask in events:
                if key.data == "waker":
                    try:
                        while self._waker_r.recv(4096):
                            pass
                    except (BlockingIOError, OSError):
                        pass
                    continue
                if key.data == "listen":
                    self._accept()
                    continue
                conn = key.data
                try:
                    self._selector.unregister(conn.sock)
                except (KeyError, ValueError, OSError):
                    continue
                try:
                    conn.enqueued_at = time.perf_counter()
                    self._work.put_nowait(conn)
                    METRICS.inc("apiserver_pool_dispatch_total")
                except queue.Full:
                    # Backpressure: bounded queue, explicit 429 — never
                    # an unbounded thread herd.
                    METRICS.inc("apiserver_pool_saturated_total")
                    try:
                        conn.sock.sendall(_SATURATED_RESPONSE)
                    except OSError:
                        pass
                    self._drop(conn)

    def _accept(self) -> None:
        for _ in range(64):  # accept bursts without starving the loop
            try:
                sock, addr = self._listen.accept()
            except (BlockingIOError, OSError):
                return
            sock.setblocking(True)
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
            conn = _Conn(sock, addr, self.server)
            with self._conns_lock:
                self._conns.add(conn)
            try:
                self._selector.register(sock, selectors.EVENT_READ, conn)
            except (KeyError, ValueError, OSError):
                self._drop(conn)

    def _drop(self, conn: _Conn) -> None:
        with self._conns_lock:
            self._conns.discard(conn)
        conn.close()

    # -- workers -------------------------------------------------------------
    # After a response, the worker LINGERS briefly on the connection: a
    # request/response client's next request lands within microseconds,
    # and serving it in place skips the selector wake + queue handoff +
    # re-register round trip (~1ms) — near thread-per-connection latency
    # for busy connections, selector parking for idle ones.  The linger
    # is skipped the moment other work is queued, so a chatty client
    # cannot monopolize a worker while others wait.
    LINGER_S = 0.002

    def _worker_loop(self) -> None:
        while True:
            conn = self._work.get()
            if conn is None or self._shutdown.is_set():
                return
            while True:
                try:
                    conn.sock.settimeout(REQUEST_TIMEOUT_S)
                    conn.handler.handle_one_request()
                except Exception as exc:
                    # A broken connection/request must never kill a pool
                    # worker; it must also never be silent (KAI007).
                    METRICS.inc("apiserver_handler_errors_total")
                    log.v(2).info("request handling failed (%s: %s)",
                                  type(exc).__name__, exc)
                    self._drop(conn)
                    conn = None
                    break
                if getattr(conn.handler, "detached", False):
                    # A watch stream took the connection to its own
                    # thread.
                    with self._conns_lock:
                        self._conns.discard(conn)
                    conn = None
                    break
                if conn.handler.close_connection:
                    self._drop(conn)
                    conn = None
                    break
                if not self._work.empty() or self._shutdown.is_set():
                    break  # others are waiting: park this conn
                try:
                    ready = selectors_select_one(conn.sock, self.LINGER_S)
                except ValueError:
                    # select() cannot poll fds >= FD_SETSIZE in a
                    # daemon-scale process: the connection is healthy —
                    # park it on the (epoll-backed) selector instead of
                    # killing the worker or the conn.
                    break
                except OSError:
                    self._drop(conn)
                    conn = None
                    break
                if not ready:
                    break  # idle: back to the selector
            if conn is not None:
                self._register(conn)


class _Handler(BaseHTTPRequestHandler):
    """One request parser/responder per connection, driven one request
    at a time by the worker pool (``handle_one_request``), never by the
    socketserver machinery."""

    protocol_version = "HTTP/1.1"

    # pylint: disable=super-init-not-called — BaseHTTPRequestHandler's
    # __init__ is the socketserver handle-immediately convention; this
    # handler is driven request-by-request by the pool instead.
    def __init__(self, conn: _Conn, server: KubeAPIServer):
        self.kai_server = server
        self.conn = conn
        self.request = conn.sock
        self.connection = conn.sock
        self.client_address = conn.addr
        self.rfile = conn.rfile
        self.wfile = conn.wfile
        self.close_connection = True
        self.detached = False
        self.suppress_response = False
        # Wire-observatory accumulator for the IN-FLIGHT request
        # (phases + byte counts); armed by _route, read by the send/
        # read helpers below.  None between requests.
        self._rq: dict | None = None

    def _send_json(self, code: int, payload: dict,
                   headers: dict | None = None) -> None:
        rq = self._rq
        t0 = time.perf_counter()
        body = _dumps(payload)
        if rq is not None:
            rq["serialize_s"] += time.perf_counter() - t0
        self._send_bytes(code, body, headers)

    def _send_bytes(self, code: int, body: bytes,
                    headers: dict | None = None) -> None:
        rq = self._rq
        if rq is not None:
            rq["status"] = code
        if getattr(self, "suppress_response", False):
            # wire-reset fault: the mutation LANDED but the connection
            # dies before a single response byte — the client faces the
            # ambiguous "did my wave land?" outcome and must resolve it
            # by idempotent replay, never by assuming failure.
            self.suppress_response = False
            self.close_connection = True
            try:
                self.conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            return
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            if v is not None:
                self.send_header(k, str(v))
        self.end_headers()
        t0 = time.perf_counter()
        self.wfile.write(body)
        if rq is not None:
            # Body bytes and the body's sendall only: the header flush
            # is one more write, identical for every response — the
            # reconciliation contract (client-sent == server-received)
            # is over BODY bytes, which framing noise would blur.
            rq["sendall_s"] += time.perf_counter() - t0
            rq["bytes_out"] += len(body)
            wireobs.count_bytes("server", rq["path"], "out", len(body))
            wireobs.count_syscall("server", rq["path"], "send")

    def _read_body(self) -> dict | None:
        length = int(self.headers.get("Content-Length") or 0)
        if not length:
            return None
        raw = self.rfile.read(length)
        rq = self._rq
        if rq is not None:
            rq["bytes_in"] += len(raw)
            wireobs.count_syscall("server", rq["path"], "recv")
        return json.loads(raw)

    def _route(self, method: str) -> None:
        """Wire-observatory shell around the real router: times the
        dispatch-queue wait / handler / serialize / sendall phases,
        counts bytes at the seams, and records one span — tagged with
        the client's injected X-Kai-Trace/X-Kai-Span context — into the
        server's bounded SpanRing.  The /debug/spans pull itself,
        /metrics scrapes, and detached watch attaches are not recorded
        (the pull would make every pull return at least its own record,
        a scrape is not control-plane traffic, and watch attaches are
        covered by per-burst fanout records)."""
        t0 = time.perf_counter()
        enqueued = self.conn.enqueued_at
        self.conn.enqueued_at = None  # linger reuse: no stale queue hop
        queue_wait = max(0.0, t0 - enqueued) if enqueued is not None \
            else 0.0
        pcls = wireobs.path_class(method, self.path)
        rq = self._rq = {"path": pcls, "bytes_in": 0, "bytes_out": 0,
                         "serialize_s": 0.0, "sendall_s": 0.0,
                         "status": None}
        trace = self.headers.get(TRACE_HEADER)
        parent = self.headers.get(SPAN_HEADER)
        try:
            self._route_inner(method)
        finally:
            self._rq = None
            if rq["bytes_in"]:
                wireobs.count_bytes("server", pcls, "in", rq["bytes_in"])
            if not self.detached \
                    and not self.path.startswith(("/debug/spans",
                                                  "/metrics")):
                elapsed = time.perf_counter() - t0
                handler_s = max(0.0, elapsed - rq["serialize_s"]
                                - rq["sendall_s"])
                self.kai_server.spans.record({
                    "trace": trace, "parent": parent,
                    "name": f"http:{pcls}", "kind": "server_request",
                    "path": pcls, "status": rq["status"],
                    "bytes_in": rq["bytes_in"],
                    "bytes_out": rq["bytes_out"],
                    "dur_s": round(queue_wait + elapsed, 6),
                    "phases": {
                        "queue_wait": round(queue_wait, 6),
                        "handler": round(handler_s, 6),
                        "serialize": round(rq["serialize_s"], 6),
                        "sendall": round(rq["sendall_s"], 6)}})

    def _route_inner(self, method: str) -> None:
        server = self.kai_server
        parsed = urlparse(self.path)
        query = {k: v[0] for k, v in parse_qs(parsed.query).items()}
        parts = [p for p in parsed.path.split("/") if p]
        if parsed.path == "/healthz":
            self._send_json(200, {"ok": True})
            return
        if parsed.path == "/metrics":
            # The apiserver process owns the server-end wire counters
            # (wire_bytes_total{end="server"}, frame_cache_bytes_total,
            # watch_fanout_*, watch_stream_queue_depth) — in the
            # split-process regime they are invisible from the
            # scheduler daemon's /metrics, so expose them here.  Writes
            # bypass _send_bytes: a scrape is not control-plane traffic
            # and must not move the byte accounting it reports.
            body = METRICS.to_prometheus_text().encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if parsed.path == "/debug/spans":
            # The scheduler-side graft pull.  Served before the wire
            # fault gates: the observatory must stay readable while the
            # wire lies — that is when its data matters most.
            try:
                after = int(query.get("since", 0))
            except ValueError:
                after = 0
            head, spans = server.spans.since(after)
            self._send_json(200, {"next": head, "spans": spans})
            return
        if parsed.path.startswith("/watch"):
            self._start_watch_stream(int(query.get("since", 0)),
                                     query.get("boot"))
            return
        if parsed.path != "/relist" \
                and server.wire_fault_fires("wire-storm", 4):
            # Throttle storm: refuse before touching the store (safe to
            # replay any method), alternating 429/503 so the client's
            # backoff handles both throttle dialects.
            with server._wire_lock:
                odd = server._wire_counts.get("wire-storm", 0) % 2
            self._send_json(429 if odd else 503,
                            {"error": "injected throttle storm"},
                            {"Retry-After": 0, "Connection": "close"})
            self.close_connection = True
            return
        if parsed.path == "/relist":
            self._send_json(200, server.relist_snapshot())
            return
        if parsed.path == "/digest":
            self._send_json(200, server.digest_snapshot())
            return
        if method != "GET" \
                and server.wire_fault_fires("wire-reset", 3, every=True):
            # Apply the mutation, then reset the connection before the
            # response (see _send_bytes) — mid-bulk-POST included.
            self.suppress_response = True
        epoch = self.headers.get("X-Kai-Epoch")
        epoch = int(epoch) if epoch is not None else None
        fence = self.headers.get("X-Kai-Fence")
        if parsed.path in ("/bulk/create", "/bulk/patch"):
            if method != "POST":
                self._send_json(405, {"error": "bulk endpoints are POST"})
                return
            code, payload, seq = server.handle_bulk(
                parts[1], self._read_body(), epoch, fence)
            self._send_json(code, payload, {"X-Kai-Seq": seq})
            return
        if not parts or parts[0] != "apis" or len(parts) < 2:
            self._send_json(404, {"error": "unknown route"})
            return
        kind = parts[1]
        namespace = parts[2] if len(parts) > 2 else None
        name = parts[3] if len(parts) > 3 else None
        if method == "GET" and name is None:
            code, body, _tok = server.handle_list(kind, query)
            self._send_bytes(code, body)
            return
        code, payload, seq = server.handle(
            method, kind, namespace or "default",
            name, query, self._read_body(), epoch=epoch, fence=fence)
        self._send_json(code, payload, {"X-Kai-Seq": seq})

    # -- watch streaming -----------------------------------------------------
    def _start_watch_stream(self, since: int, boot: str | None) -> None:
        """Detach the connection onto a dedicated streamer thread: watch
        streams live for the client's lifetime and must not occupy pool
        workers (a fleet of watchers would deadlock the pool)."""
        server = self.kai_server
        slot = server.acquire_watch_slot()
        if slot is None:
            METRICS.inc("apiserver_watch_streams_rejected_total")
            self._send_json(429, {"error": "watch stream limit reached"},
                            {"Retry-After": 1})
            return
        self.detached = True
        t = threading.Thread(target=self._stream_watch_detached,
                             args=(since, boot, slot), daemon=True,
                             name="apiserver-watch-stream")
        t.start()

    def _stream_watch_detached(self, since: int, boot: str | None,
                               slot: int) -> None:
        try:
            self.conn.sock.settimeout(REQUEST_TIMEOUT_S)
            self._stream_watch(since, boot, slot)
        finally:
            self.kai_server.release_watch_slot(slot)
            self.conn.close()

    def _stream_watch(self, since: int, boot: str | None,
                      slot: int) -> None:
        server = self.kai_server
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def send_line(payload: dict) -> None:
            line = _chunk(_dumps(payload) + b"\n")
            self.wfile.write(line)
            wireobs.count_bytes("server", "watch", "out", len(line))
            wireobs.count_syscall("server", "watch", "send")

        # Chaos: drop the stream after N lines (watchdrop fault) —
        # the client must reconnect with its seq and lose nothing.
        drop_spec = control_fault("watchdrop")
        drop_after = (int(drop_spec) if drop_spec else 5) \
            if drop_spec is not None else None
        # Wire faults (CONTROL_FAULT_MODES): truncate a frame mid-chunk
        # after N, corrupt every Nth frame's payload (framing intact),
        # stall before every batch write.  All per-stream counters —
        # each reconnect faces the fault again, which is the point.
        trunc_spec = control_fault("wire-truncate")
        trunc_after = (int(trunc_spec) if trunc_spec else 5) \
            if trunc_spec is not None else None
        corrupt_spec = control_fault("wire-corrupt")
        corrupt_every = (int(corrupt_spec) if corrupt_spec else 7) \
            if corrupt_spec is not None else None
        stall_spec = control_fault("wire-stall")
        stall_s = (float(stall_spec or 50) / 1000.0) \
            if stall_spec is not None else None
        depth_cap = wireobs.watch_queue_cap()
        sent = 0
        seq = since
        try:
            # Resumption from outside the ring's retained window: the
            # history is gone — the requested events were evicted
            # (since < oldest), or this server restarted (boot-id
            # mismatch; seq numbers from the previous life mean
            # nothing here, INCLUDING when the new log's head has
            # already caught up past the client's cursor).  K8s
            # answers 410 Gone and the informer re-lists; we send
            # one explicit GONE line and close.  Never silently
            # replay a truncated history.
            if server.wire_fault_fires("wire-gone", 3):
                # Compaction storm: answer GONE regardless of cursor —
                # every affected client pays a full re-list, and the
                # reconnect backoff must keep the herd from arriving in
                # lockstep (tests/test_wire_protocol.py).
                send_line({"type": "GONE", "code": 410,
                           "seq": server.log.seq,
                           "boot": server.boot_id,
                           "oldest": server.log.oldest()})
                return
            restarted = boot is not None and boot != server.boot_id
            if restarted or seq < server.log.oldest() \
                    or seq > server.log.seq:
                send_line({"type": "GONE", "code": 410,
                           "seq": server.log.seq,
                           "boot": server.boot_id,
                           "oldest": server.log.oldest()})
                return
            send_line({"type": "BOOT", "boot": server.boot_id,
                       "seq": seq})
            while not server._closing.is_set():
                events = server.log.since(seq)
                # Send-queue depth: frames pending behind this
                # watcher's cursor, ABOUT to be buffered into one
                # burst.  Beyond the cap the watcher is too slow to
                # keep a bounded buffer — answer an explicit GONE
                # (it re-lists and resumes from head) instead of
                # accumulating the ring into an in-flight bytearray,
                # which was this streamer's unbounded-memory blind
                # spot.
                wireobs.note_stream_depth(slot, len(events))
                if len(events) > depth_cap:
                    METRICS.inc("watch_stream_depth_gone_total")
                    send_line({"type": "GONE", "code": 410,
                               "seq": server.log.seq,
                               "boot": server.boot_id,
                               "oldest": server.log.oldest(),
                               "reason": "send queue depth "
                                         f"{len(events)} > {depth_cap}"})
                    return
                if events and events[0][0] != seq + 1:
                    # This watcher overran the ring mid-stream: the
                    # events between its cursor and the retained
                    # window were evicted while it stalled.  Same
                    # contract as resume-from-outside-the-window:
                    # one explicit GONE line, then close — the
                    # client re-lists.  Never silently skip history.
                    send_line({"type": "GONE", "code": 410,
                               "seq": server.log.seq,
                               "boot": server.boot_id,
                               "oldest": server.log.oldest()})
                    return
                # One write per batch of PRESERIALIZED chunks: the
                # object bytes were encoded once at append time; every
                # watcher fans the same buffer out verbatim (wfile is
                # unbuffered, so the burst leaves in one sendall).
                buf = bytearray()
                dropped = False
                truncated = False
                n_frames = 0
                for eseq, _etype, _obj, chunk in events:
                    sent += 1
                    if truncated is False and trunc_after is not None \
                            and sent > trunc_after:
                        # Truncation: HALF of this frame's bytes, then
                        # the connection dies — the client must treat
                        # the torn tail as stream death and resume from
                        # its last DELIVERED seq (never this one).
                        METRICS.inc("wire_faults_injected_total",
                                    mode="wire-truncate")
                        buf += chunk[:max(1, len(chunk) // 2)]
                        truncated = True
                        break
                    if corrupt_every is not None \
                            and sent % corrupt_every == 0:
                        METRICS.inc("wire_faults_injected_total",
                                    mode="wire-corrupt")
                        chunk = _corrupt_chunk(chunk)
                    buf += chunk
                    seq = eseq
                    n_frames += 1
                    if drop_after is not None and sent >= drop_after:
                        dropped = True  # injected mid-stream drop
                        break
                if buf:
                    if stall_s is not None:
                        METRICS.inc("wire_faults_injected_total",
                                    mode="wire-stall")
                        time.sleep(stall_s)
                    # Counted before the bytes leave: a client that has
                    # read its frames must find them counted.
                    METRICS.inc("watch_frame_cache_hits_total", n_frames)
                    t_burst = time.perf_counter()
                    self.wfile.write(buf)
                    burst_s = time.perf_counter() - t_burst
                    # Fanout accounting: the burst left in ONE sendall
                    # of preserialized (cache-served) bytes; lag is
                    # what already accumulated behind this watcher
                    # while it was being written.
                    wireobs.count_bytes("server", "watch", "out",
                                        len(buf))
                    wireobs.count_syscall("server", "watch", "send")
                    wireobs.count_frame_bytes("cache", len(buf))
                    lag = server.log.seq - seq
                    wireobs.note_fanout(slot, n_frames, len(buf), lag)
                    server.spans.record({
                        "trace": None, "parent": None,
                        "name": "watch:fanout",
                        "kind": "server_fanout", "path": "watch",
                        "stream": slot, "frames": n_frames,
                        "lag_frames": lag, "bytes_out": len(buf),
                        "dur_s": round(burst_s, 6),
                        "phases": {"sendall": round(burst_s, 6)}})
                if dropped or truncated:
                    return
                with server.log.cond:
                    if server.log.seq == seq \
                            and not server._closing.is_set():
                        server.log.cond.wait(timeout=HEARTBEAT_SECONDS)
                if not events and not server._closing.is_set():
                    send_line({"type": "HEARTBEAT", "seq": seq})
        except (BrokenPipeError, ConnectionResetError, OSError):
            return

    def do_GET(self):
        self._route("GET")

    def do_POST(self):
        self._route("POST")

    def do_PUT(self):
        self._route("PUT")

    def do_PATCH(self):
        self._route("PATCH")

    def do_DELETE(self):
        self._route("DELETE")

    def log_message(self, *args):
        pass


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser("kai-apiserver")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8443)
    ap.add_argument("--pool-size", type=int, default=POOL_SIZE)
    args = ap.parse_args(argv)
    server = KubeAPIServer(host=args.host, port=args.port,
                           pool_size=args.pool_size)
    print(f"kai-apiserver listening on {server.url}", flush=True)
    server.httpd.serve_forever()


if __name__ == "__main__":
    main()
