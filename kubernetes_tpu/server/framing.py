"""Compact length-prefixed binary framing for the fleet verbs (ISSUE 11).

The fleet wall is the transport, not the payload:
a NO-OP ThreadingHTTPServer measures ~196 req/s with 100 clients on the
2-core box while the service answers a warm scheduleOne step in
~0.2-6 ms. This module is the wire half of killing that wall — a
hand-rolled struct encoding (pure stdlib, no msgpack dependency) for the
verbs the fleet actually speaks, served by the single-threaded async
event loop in server/asyncwire.py and driven by the blocking fleet
client in client/binarywire.py.

Frame layout (network byte order)::

    u32  length    # bytes AFTER this field: 6-byte header rest + payload
    u8   verb      # request 0x01-0x06, response 0x81-0x89
    u8   flags     # FLAG_COMPACT on FILTER: elide the all-passed echo
    u32  request_id  # client correlation id, echoed verbatim in the
                     # response (a pipelining frontend matches on it)
    ...  payload   # verb-specific, primitives below

Primitives: u8/u16/u32, i64, str (u32 length + utf-8), blob (u32 length
+ raw bytes). Every read is bounds-checked: a truncated or corrupt
payload raises the typed ``FrameError`` instead of an IndexError deep in
struct — the async server answers it with an ERROR frame (payload decode)
or drops the connection (unrecoverable stream desync on a corrupt length
prefix), and the frame fuzzer in tests/test_framing.py pins both.

Verbs — requests:

    FILTER      fused filter+topk on ONE ticket (the binary twin of the
                HTTP ``/filter {"Compact", "TopK"}`` extension): u16
                top_k, u32 deadline_ms (0 = none), pod blob. The
                response is VERDICT.
    BIND        spec-carrying commit: pod_name, namespace, uid, node,
                i64 snapshot_gen (-1 = none), idempotency key (the
                BindLedger key rides the frame, "" = none), u32
                deadline_ms, optional pod blob (exact fence math).
                Response: BIND_RESULT.
    SYNC_NODES / SYNC_PODS
                bulk cache sync. Payload: u8 codec tag + blob — tag 1 is
                the existing api/protowire protobuf codec when available,
                tag 0 the JSON item list (the negotiable fallback, same
                as the HTTP Content-Type switch). Response: SYNCED.
    METRICS     -> METRICS_TEXT (the Prometheus text the HTTP /metrics
                serves).
    PING        -> PONG, no service touch — the no-op round trip, the
                wire's floor against the threaded-HTTP no-op floor.
    STATS       live introspection (ISSUE 13): u32 last_n ->
                STATS_RESULT carrying the unified telemetry-registry
                snapshot plus the flight recorder's last_n events as a
                JSON blob — identical content to HTTP /debug/vars +
                /debug/trace and the embedded debug_snapshot.
    RELIST      cell-truth pull (ISSUE 16): no payload ->
                RELIST_RESULT carrying two codec-tagged item blobs —
                live nodes, then every pod the shared cache charges to a
                node — so a scheduler PROCESS refreshes its own
                bounded-stale snapshot without the server pushing state
                (the level-triggered re-list of the watch/relist
                discipline, over the wire).
    CELL_AGG    federation aggregate pull (ISSUE 20): u8 verb flags
                (drain spill / evacuate pending) -> CELL_AGG_RESULT
                carrying the cell's incrementally-maintained aggregate
                (JSON blob) + the spilled/evacuated pods it hands back
                for re-routing (codec-tagged items blob).
    ADMIT       federation admission (ISSUE 20): idempotency key + pod
                batch -> ADMIT_RESULT (accepted, replayed counts). A
                pod that already exists in the cell's store is a REPLAY,
                never a second admission.

Verbs — responses:

    VERDICT     i64 snapshot_gen, u8 all_passed, u32 passed_count,
                passed names (empty under FLAG_COMPACT+all_passed — the
                5k-name echo is the single biggest JSON-wire cost),
                failed names, top scores [(host, i64 score)].
    BIND_RESULT u8 kind (0 ok, 1 conflict, 2 pending, 3 shed, 4 error),
                u32 retry_after_ms, error string — the typed
                conflict/backoff contract of bind_verdict, verbatim.
    OVERLOADED  u32 retry_after_ms, jittered server-side: the typed
                backpressure frame (the HTTP 429 + Retry-After twin).
    DEADLINE    the request outlived its own deadline while queued
                (the HTTP 504 twin); nothing was evaluated.
    ERROR       str message — typed in-band failure, connection stays
                usable (payload-level errors only; stream-level
                corruption closes the connection instead).

All correctness semantics live BELOW this codec (fence, ledger,
staleness, coalescing — server/extender.py, server/embedded.py);
swapping the wire moves no semantics, which tests/test_asyncwire.py
pins by re-running the ISSUE 9 fault storms over this framing.
"""

from __future__ import annotations

import json
import struct
from typing import Dict, List, Optional, Tuple

# ------------------------------------------------------------------ verbs

FILTER = 0x01
BIND = 0x02
SYNC_NODES = 0x03
SYNC_PODS = 0x04
METRICS = 0x05
PING = 0x06
# live introspection (ISSUE 13): u32 last_n (0 = vars only) -> the
# unified telemetry-registry snapshot + the flight recorder's event
# tail, identical content to HTTP /debug/vars + /debug/trace and the
# embedded debug_snapshot — the wire twin of Borg's per-task
# introspection endpoints
STATS = 0x07
# cell-truth pull (ISSUE 16): the inverse of the SYNC push — a worker
# process relists (nodes, bound pods) from the shared cell to refresh
# its own scheduler's bounded-stale snapshot
RELIST = 0x08
# federation verbs (ISSUE 20): the front-door router's two touches of a
# member cell. CELL_AGG pulls the cell's incrementally-maintained
# aggregate (capacity headroom, band pressure, affinity domains — the
# [C, M] routing tensor's one column) plus any spilled pods the cell
# wants re-routed; flags in the payload ask for spill drain and/or a
# full pending evacuation (brownout). ADMIT hands a batch of pods to
# exactly one cell under an idempotency key — replays are counted, not
# re-created, so a lost ADMIT_RESULT re-send cannot double-admit.
CELL_AGG = 0x09
ADMIT = 0x0A

VERDICT = 0x81
BIND_RESULT = 0x82
OVERLOADED = 0x84
DEADLINE = 0x85
ERROR = 0x86
SYNCED = 0x87
METRICS_TEXT = 0x88
PONG = 0x89
STATS_RESULT = 0x8A
RELIST_RESULT = 0x8B
CELL_AGG_RESULT = 0x8C
ADMIT_RESULT = 0x8D

FLAG_COMPACT = 0x01
# trace context on FILTER/BIND (ISSUE 15): when set, the payload is
# PREFIXED with one str field — the pod-trace id — so a fleet
# scheduleOne's filter->bind hops join one podtrace timeline across the
# wire. Presence IS the sample decision (the client made the head call);
# a server without the tracer armed skips the id in O(1).
FLAG_TRACE = 0x02

BIND_KINDS = ("ok", "conflict", "pending", "shed", "error")
_BIND_KIND_CODE = {k: i for i, k in enumerate(BIND_KINDS)}

# codec tags for object blobs (pods / node lists): the existing protobuf
# path when its bindings exist, JSON otherwise — the binary FRAMING is
# independent of the payload codec, exactly like the HTTP Content-Type
# negotiation it replaces
CODEC_JSON = 0
CODEC_PROTO = 1

# header: length(u32) covers verb+flags+request_id+payload
_HDR = struct.Struct("!IBBI")
HEADER_SIZE = _HDR.size  # 10
_LEN_REST = HEADER_SIZE - 4  # verb+flags+request_id = 6

# a 5k-node JSON node list is a few MB; 64 MiB bounds any legitimate
# sync while making a corrupt length prefix (e.g. ASCII read as u32)
# detectable immediately instead of a multi-GB allocation
MAX_FRAME = 64 << 20


class FrameError(Exception):
    """Typed framing failure: corrupt length, truncated payload, unknown
    structure. Payload-scoped errors keep the connection; a corrupt
    length prefix is a stream desync and closes it."""


# ------------------------------------------------------------- primitives


class Writer:
    """Append-only payload builder over one bytearray."""

    __slots__ = ("buf",)

    def __init__(self) -> None:
        self.buf = bytearray()

    def u8(self, v: int) -> "Writer":
        self.buf.append(v & 0xFF)
        return self

    def u16(self, v: int) -> "Writer":
        self.buf += struct.pack("!H", v)
        return self

    def u32(self, v: int) -> "Writer":
        self.buf += struct.pack("!I", v)
        return self

    def i64(self, v: int) -> "Writer":
        self.buf += struct.pack("!q", v)
        return self

    def str_(self, s: str) -> "Writer":
        b = s.encode("utf-8")
        self.u32(len(b))
        self.buf += b
        return self

    def blob(self, b: bytes) -> "Writer":
        self.u32(len(b))
        self.buf += b
        return self

    def strs(self, items) -> "Writer":
        self.u32(len(items))
        for s in items:
            self.str_(s)
        return self


class Reader:
    """Bounds-checked cursor over one frame payload — every underrun is
    the typed FrameError, never a silent short read."""

    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes) -> None:
        self.buf = buf
        self.pos = 0

    def _take(self, n: int) -> bytes:
        if self.pos + n > len(self.buf):
            raise FrameError(
                f"truncated payload: need {n} bytes at offset {self.pos}, "
                f"have {len(self.buf) - self.pos}")
        b = self.buf[self.pos:self.pos + n]
        self.pos += n
        return b

    def u8(self) -> int:
        return self._take(1)[0]

    def u16(self) -> int:
        return struct.unpack("!H", self._take(2))[0]

    def u32(self) -> int:
        return struct.unpack("!I", self._take(4))[0]

    def i64(self) -> int:
        return struct.unpack("!q", self._take(8))[0]

    def str_(self) -> str:
        n = self.u32()
        if n > len(self.buf) - self.pos:
            raise FrameError(f"truncated string: declared {n} bytes, "
                             f"have {len(self.buf) - self.pos}")
        return bytes(self._take(n)).decode("utf-8", errors="replace")

    def blob(self) -> bytes:
        n = self.u32()
        if n > len(self.buf) - self.pos:
            raise FrameError(f"truncated blob: declared {n} bytes, "
                             f"have {len(self.buf) - self.pos}")
        return bytes(self._take(n))

    def strs(self) -> List[str]:
        n = self.u32()
        # each entry needs >= 4 length bytes: reject absurd counts before
        # looping (a corrupt count must not spin building a giant list)
        if n > (len(self.buf) - self.pos) // 4 + 1:
            raise FrameError(f"corrupt list count {n}")
        return [self.str_() for _ in range(n)]


# ---------------------------------------------------------- trace context


def wrap_trace(payload: bytes, trace_id: str) -> bytes:
    """Prefix a FILTER/BIND payload with the pod-trace id (the sender
    also sets FLAG_TRACE on the frame)."""
    return bytes(Writer().str_(trace_id).buf) + payload


def unwrap_trace(payload: bytes, flags: int):
    """(trace_id | None, payload rest): strips the FLAG_TRACE prefix
    when present, returns the payload untouched otherwise."""
    if not (flags & FLAG_TRACE):
        return None, payload
    r = Reader(payload)
    tid = r.str_()
    return tid, payload[r.pos:]


# ----------------------------------------------------------------- frames


def encode_frame(verb: int, request_id: int, payload: bytes = b"",
                 flags: int = 0) -> bytes:
    return _HDR.pack(_LEN_REST + len(payload), verb, flags,
                     request_id) + payload


class FrameDecoder:
    """Incremental stream decoder: feed() arbitrary chunks (interleaved
    partial writes included), get complete frames back. A corrupt length
    prefix raises FrameError — the stream cannot be resynced past it."""

    __slots__ = ("_buf", "max_frame")

    def __init__(self, max_frame: int = MAX_FRAME) -> None:
        self._buf = bytearray()
        self.max_frame = max_frame

    def feed(self, data: bytes) -> List[Tuple[int, int, int, bytes]]:
        """Returns complete frames as (verb, flags, request_id, payload)."""
        self._buf += data
        out = []
        while len(self._buf) >= HEADER_SIZE:
            length, verb, flags, req_id = _HDR.unpack_from(self._buf, 0)
            if length < _LEN_REST or length > self.max_frame:
                raise FrameError(f"corrupt frame length {length} "
                                 f"(bounds {_LEN_REST}..{self.max_frame})")
            total = 4 + length
            if len(self._buf) < total:
                break  # partial frame: wait for more bytes
            payload = bytes(self._buf[HEADER_SIZE:total])
            del self._buf[:total]
            out.append((verb, flags, req_id, payload))
        return out

    @property
    def buffered(self) -> int:
        return len(self._buf)


# -------------------------------------------------------------- pod blobs


def _proto_available() -> bool:
    try:
        from kubernetes_tpu.api import protowire
        return protowire.available()
    except Exception:
        return False


def encode_pod_blob(pod) -> bytes:
    """One pod, protobuf when the bindings exist, JSON serde otherwise."""
    if _proto_available():
        from kubernetes_tpu.api import protowire
        return bytes([CODEC_PROTO]) + protowire.encode_pods([pod])
    from kubernetes_tpu.api import serde
    return bytes([CODEC_JSON]) + json.dumps(
        serde.encode_pod(pod), separators=(",", ":")).encode()


def decode_pod_blob(blob: bytes):
    if not blob:
        raise FrameError("empty pod blob")
    tag, body = blob[0], blob[1:]
    if tag == CODEC_PROTO:
        from kubernetes_tpu.api import protowire
        if not protowire.available():
            raise FrameError("protobuf pod blob but bindings unavailable")
        pods = protowire.decode_pods(body)
        if len(pods) != 1:
            raise FrameError(f"pod blob holds {len(pods)} pods, want 1")
        return pods[0]
    if tag == CODEC_JSON:
        from kubernetes_tpu.api import serde
        try:
            return serde.decode_pod(json.loads(body))
        except (ValueError, KeyError, TypeError) as e:
            raise FrameError(f"bad JSON pod blob: {e}") from e
    raise FrameError(f"unknown pod codec tag {tag}")


def encode_items_blob(items, kind: str) -> bytes:
    """Bulk node/pod list for the SYNC verbs, codec-negotiated like the
    HTTP bulk endpoints (protowire Content-Type vs JSON)."""
    if _proto_available():
        from kubernetes_tpu.api import protowire
        enc = (protowire.encode_nodes if kind == "nodes"
               else protowire.encode_pods)
        return bytes([CODEC_PROTO]) + enc(items)
    from kubernetes_tpu.api import serde
    enc1 = serde.encode_node if kind == "nodes" else serde.encode_pod
    return bytes([CODEC_JSON]) + json.dumps(
        [enc1(i) for i in items], separators=(",", ":")).encode()


def decode_items_blob(blob: bytes, kind: str):
    if not blob:
        raise FrameError("empty items blob")
    tag, body = blob[0], blob[1:]
    if tag == CODEC_PROTO:
        from kubernetes_tpu.api import protowire
        if not protowire.available():
            raise FrameError("protobuf items blob but bindings unavailable")
        return (protowire.decode_nodes(body) if kind == "nodes"
                else protowire.decode_pods(body))
    if tag == CODEC_JSON:
        from kubernetes_tpu.api import serde
        dec1 = serde.decode_node if kind == "nodes" else serde.decode_pod
        try:
            return [dec1(o) for o in json.loads(body)]
        except (ValueError, KeyError, TypeError) as e:
            raise FrameError(f"bad JSON items blob: {e}") from e
    raise FrameError(f"unknown items codec tag {tag}")


# --------------------------------------------------------------- requests


def encode_filter_request(pod, top_k: int = 0, deadline_ms: int = 0,
                          pod_blob: Optional[bytes] = None) -> bytes:
    """``pod_blob`` lets a retrying client amortize the spec encoding
    across attempts (the blob is deterministic per spec — exactly the
    candidate-list-serialized-once discipline of the HTTP drivers)."""
    return bytes(Writer().u16(top_k).u32(deadline_ms)
                 .blob(pod_blob if pod_blob is not None
                       else encode_pod_blob(pod)).buf)


def decode_filter_request(payload: bytes):
    blob, top_k, deadline_ms = decode_filter_request_lazy(payload)
    return decode_pod_blob(blob), top_k, deadline_ms


def decode_filter_request_lazy(payload: bytes):
    """Header fields now, pod blob LATER: the async server parses frames
    on the event loop but defers the (comparatively expensive) pod
    decode to the worker — and caches it, since the same spec blob
    arrives once per verb and once per retry."""
    r = Reader(payload)
    top_k = r.u16()
    deadline_ms = r.u32()
    return r.blob(), top_k, deadline_ms


def encode_bind_request(pod_name: str, namespace: str, uid: str, node: str,
                        snapshot_gen: Optional[int] = None,
                        idem_key: str = "", deadline_ms: int = 0,
                        pod=None, pod_blob: Optional[bytes] = None) -> bytes:
    w = (Writer().str_(pod_name).str_(namespace).str_(uid).str_(node)
         .i64(-1 if snapshot_gen is None else snapshot_gen)
         .str_(idem_key).u32(deadline_ms))
    if pod_blob is not None:
        w.blob(pod_blob)
    else:
        w.blob(encode_pod_blob(pod) if pod is not None else b"")
    return bytes(w.buf)


def decode_bind_request(payload: bytes):
    out = decode_bind_request_lazy(payload)
    blob = out[-1]
    return out[:-1] + (decode_pod_blob(blob) if blob else None,)


def decode_bind_request_lazy(payload: bytes):
    """Like decode_filter_request_lazy: everything but the pod decode."""
    r = Reader(payload)
    name, ns, uid, node = r.str_(), r.str_(), r.str_(), r.str_()
    gen = r.i64()
    idem_key = r.str_()
    deadline_ms = r.u32()
    blob = r.blob()
    return (name, ns, uid, node, None if gen < 0 else gen,
            idem_key or None, deadline_ms, blob)


def encode_sync_request(items, kind: str) -> bytes:
    return encode_items_blob(items, kind)


# -------------------------------------------------------------- responses


def encode_verdict(gen: Optional[int], all_passed: bool, passed_count: int,
                   passed: Optional[List[str]], failed: List[str],
                   top: List[Tuple[str, int]]) -> bytes:
    w = (Writer().i64(-1 if gen is None else gen)
         .u8(1 if all_passed else 0).u32(passed_count)
         .strs(passed or []).strs(failed))
    w.u32(len(top))
    for host, score in top:
        w.str_(host).i64(int(score))
    return bytes(w.buf)


def decode_verdict(payload: bytes):
    r = Reader(payload)
    gen = r.i64()
    all_passed = bool(r.u8())
    passed_count = r.u32()
    passed = r.strs()
    failed = r.strs()
    top = [(r.str_(), r.i64()) for _ in range(r.u32())]
    return {"gen": None if gen < 0 else gen, "all_passed": all_passed,
            "passed_count": passed_count, "passed": passed,
            "failed": failed, "top": top}


def encode_bind_result(kind: str, retry_after_ms: int, error: str) -> bytes:
    return bytes(Writer().u8(_BIND_KIND_CODE[kind]).u32(retry_after_ms)
                 .str_(error).buf)


def decode_bind_result(payload: bytes):
    r = Reader(payload)
    code = r.u8()
    if code >= len(BIND_KINDS):
        raise FrameError(f"unknown bind-result kind {code}")
    return {"kind": BIND_KINDS[code], "retry_after_ms": r.u32(),
            "error": r.str_()}


def encode_overloaded(retry_after_ms: int) -> bytes:
    return bytes(Writer().u32(retry_after_ms).buf)


def decode_overloaded(payload: bytes) -> int:
    return Reader(payload).u32()


def encode_error(message: str) -> bytes:
    return bytes(Writer().str_(message).buf)


def decode_error(payload: bytes) -> str:
    return Reader(payload).str_()


def encode_synced(count: int) -> bytes:
    return bytes(Writer().u32(count).buf)


def decode_synced(payload: bytes) -> int:
    return Reader(payload).u32()


def encode_metrics_text(text: str) -> bytes:
    return bytes(Writer().str_(text).buf)


def decode_metrics_text(payload: bytes) -> str:
    return Reader(payload).str_()


def encode_stats_request(last: int = 0) -> bytes:
    """STATS request: how many trailing recorder events to include
    (0 = registry vars only)."""
    return bytes(Writer().u32(last).buf)


def decode_stats_request(payload: bytes) -> int:
    return Reader(payload).u32()


def encode_stats_result(obj: Dict) -> bytes:
    """STATS_RESULT: {"vars": <registry snapshot>, "trace": [events]}
    as one JSON blob — introspection is a debug verb; the payload's
    open-ended key set does not justify a bespoke struct layout."""
    return bytes(Writer().blob(json.dumps(
        obj, separators=(",", ":")).encode()).buf)


def decode_stats_result(payload: bytes) -> Dict:
    try:
        return json.loads(Reader(payload).blob())
    except ValueError as e:
        raise FrameError(f"bad STATS payload: {e}") from e


def encode_relist_result(nodes, pods) -> bytes:
    """RELIST_RESULT: two codec-tagged item blobs — live nodes, then the
    bound pods the shared cache charges to them (ISSUE 16). Each rides
    its own length prefix so the reader never guesses a boundary."""
    return bytes(Writer().blob(encode_items_blob(nodes, "nodes"))
                 .blob(encode_items_blob(pods, "pods")).buf)


def decode_relist_result(payload: bytes):
    r = Reader(payload)
    return (decode_items_blob(r.blob(), "nodes"),
            decode_items_blob(r.blob(), "pods"))


# ------------------------------------------------------- federation verbs

# CELL_AGG request flag bits (payload u8, not frame flags: frame flags
# are transport-scoped, these are verb semantics)
CELL_DRAIN_SPILL = 0x01   # include + consume the cell's spill buffer
CELL_EVACUATE = 0x02      # brownout: ALSO uproot every pending pod


def encode_cell_agg_request(drain_spill: bool = False,
                            evacuate: bool = False) -> bytes:
    f = (CELL_DRAIN_SPILL if drain_spill else 0) \
        | (CELL_EVACUATE if evacuate else 0)
    return bytes(Writer().u8(f).buf)


def decode_cell_agg_request(payload: bytes) -> Tuple[bool, bool]:
    f = Reader(payload).u8()
    return bool(f & CELL_DRAIN_SPILL), bool(f & CELL_EVACUATE)


def encode_cell_agg_result(agg: Dict, spilled) -> bytes:
    """CELL_AGG_RESULT: the aggregate as one JSON blob (an open-ended,
    evolving key set — the STATS rationale) + a codec-tagged items blob
    of pods the cell hands back for re-routing (spill drain/evacuation;
    empty when the request asked for neither)."""
    return bytes(Writer()
                 .blob(json.dumps(agg, separators=(",", ":")).encode())
                 .blob(encode_items_blob(list(spilled), "pods")
                       if spilled else b"").buf)


def decode_cell_agg_result(payload: bytes):
    r = Reader(payload)
    try:
        agg = json.loads(r.blob())
    except ValueError as e:
        raise FrameError(f"bad CELL_AGG payload: {e}") from e
    blob = r.blob()
    return agg, (decode_items_blob(blob, "pods") if blob else [])


def encode_admit_request(idem_key: str, pods) -> bytes:
    return bytes(Writer().str_(idem_key)
                 .blob(encode_items_blob(list(pods), "pods")).buf)


def decode_admit_request(payload: bytes):
    r = Reader(payload)
    idem_key = r.str_()
    return idem_key, decode_items_blob(r.blob(), "pods")


def encode_admit_result(accepted: int, replayed: int) -> bytes:
    return bytes(Writer().u32(accepted).u32(replayed).buf)


def decode_admit_result(payload: bytes) -> Tuple[int, int]:
    r = Reader(payload)
    return r.u32(), r.u32()


__all__ = [
    "ADMIT", "ADMIT_RESULT",
    "BIND", "BIND_KINDS", "BIND_RESULT",
    "CELL_AGG", "CELL_AGG_RESULT", "CELL_DRAIN_SPILL", "CELL_EVACUATE",
    "CODEC_JSON", "CODEC_PROTO",
    "DEADLINE", "ERROR", "FILTER", "FLAG_COMPACT", "FLAG_TRACE",
    "FrameDecoder",
    "FrameError", "HEADER_SIZE", "MAX_FRAME", "METRICS", "METRICS_TEXT",
    "OVERLOADED", "PING", "PONG", "RELIST", "RELIST_RESULT", "Reader",
    "STATS", "STATS_RESULT",
    "SYNCED", "SYNC_NODES", "SYNC_PODS", "VERDICT", "Writer",
    "decode_admit_request", "decode_admit_result",
    "decode_bind_request", "decode_bind_request_lazy",
    "decode_bind_result",
    "decode_cell_agg_request", "decode_cell_agg_result",
    "decode_error", "decode_filter_request",
    "decode_filter_request_lazy", "decode_items_blob",
    "decode_metrics_text", "decode_overloaded", "decode_pod_blob",
    "decode_relist_result",
    "decode_stats_request", "decode_stats_result", "decode_synced",
    "decode_verdict",
    "encode_admit_request", "encode_admit_result",
    "encode_bind_request", "encode_bind_result",
    "encode_cell_agg_request", "encode_cell_agg_result",
    "encode_error", "encode_filter_request", "encode_frame",
    "encode_items_blob", "encode_metrics_text", "encode_overloaded",
    "encode_pod_blob", "encode_relist_result", "encode_stats_request",
    "encode_stats_result",
    "encode_sync_request", "encode_synced", "encode_verdict",
    "unwrap_trace", "wrap_trace",
]
