"""TPU slab sidecar: one device-owner process, many wire frontends.

Why this exists: a single Python process tops out at a few thousand RPS of
gRPC handling (GIL + per-RPC overhead), while the slab engine does millions
of decisions per launch. The reference scales its wire layer by running
2-3 stateless replicas against one shared Redis (nomad/apigw-ratelimit/
common.hcl:2) — the Redis process is the shared single-writer state. Here
the TPU chip plays Redis's role: ONE sidecar process owns the slab
(SlabDeviceEngine, backends/tpu.py) and N frontend processes — each a full
gRPC/HTTP server bound to the same ports via SO_REUSEPORT — ship item
batches to it over a unix socket. The sidecar's dispatch loop coalesces
across ALL frontends, so more frontends means BIGGER device batches, not
contention. Limits stay globally exact because every increment serializes
through the one slab, exactly like N replicas against one Redis.

The server runs the engine in block mode: the wire payload's uint32[6, n]
block goes to the device input with numpy row copies only — no per-item
Python objects anywhere on the aggregation path (the item path's decode +
repack cost ~2.3us/item of pure Python — an ~0.4M items/s server ceiling
at batch 8k with device time included; block-native measures ~8x that on
the same host, and the gap widens on a real chip where device time stops
masking host time).

This is the "JAX/TPU sidecar" of the north star (BASELINE.json).

Wire protocol (length-framed, little-endian, one in-flight request per
connection; frontends pool connections for concurrency):

  request:  u32 magic 'RLSC' | u8 version=1 | u8 op | u16 flags
            op 1 SUBMIT: u32 n | uint32[6, n] C-order
                         rows: fp_lo, fp_hi, hits, limit, divider, jitter
                         (the divider word carries the rule's decision-
                         algorithm id in bits 28-30 — ops/slab.py ALGO_* —
                         including concurrency Release riders (id 4), so
                         the algorithm subsystem rides this wire with
                         ZERO format change; fixed_window is id 0 and
                         pre-algorithm frames are bit-identical)
                         flags bit 1 (FLAG_LEASE): a lease-ops trailer
                         follows the block — u32 len | the LeaseOps body
                         (backends/lease.py encode_lease_ops: grant/renew
                         riders referencing block columns plus settle
                         records), read BEFORE the trace trailer. The
                         grants' INCRBY is already in the hits column;
                         the trailer is the liability bookkeeping the
                         device owner registers after the launch.
                         flags bit 0 (FLAG_TRACE): a B3 trace trailer
                         follows (after the lease trailer when both) —
                         u32 len | the TextMap carrier
                         (tracing/propagation.py inject, newline-joined
                         `header:value` lines), so the frontend-process
                         span parents the device-owner-process spans
                         across the RPC. Untraced frames carry flags=0
                         and zero extra bytes.
            op 2 PING:   empty
            op 3 REPL_SUBSCRIBE: u32 epoch | u64 last_seq — a warm
                         standby subscribing (persist/replication.py).
                         The server acks one status byte, then STREAMS
                         sequence-numbered replication frames (full
                         snapshot first, dirty-row deltas on the
                         REPL_INTERVAL_MS cadence) on this connection.
                         flags bit 2 (FLAG_EPOCH): a u32 epoch trailer
                         follows the block (after the lease trailer,
                         before the trace trailer) — the split-brain
                         fence. Only multi-address clients
                         (SIDECAR_ADDRS) set it, so single-address
                         deployments ship byte-identical legacy frames.
  response: u8 status (0 ok / 1 error / 2 ok+epoch / 3 stale epoch)
            SUBMIT ok:   u32 n | uint32[n] post-increment counters
            ok+epoch:    u32 epoch | u32 n | uint32[n] counters — only
                         ever answers FLAG_EPOCH frames (how a failed-
                         over client learns the promoted epoch)
            stale epoch: u32 server_epoch — the frame carried a NEWER
                         epoch than this owner serves: it is a
                         resurrected stale primary and the write was
                         NOT applied (counted repl.stale_epoch_rejected)
            PING ok:     empty
            error:       u32 len | utf-8 message

`now` is stamped by the sidecar at launch time — one clock authority, so
frontends never disagree about window boundaries.

Transports (the address string selects one):

  /path/to.sock        unix socket — same-host frontends (default)
  tcp://host:port      TCP — frontends on OTHER hosts, the DCN analog of
                       the reference's N replicas dialing one shared Redis
                       over the network (src/redis/driver_impl.go:60-78,
                       nomad/apigw-ratelimit/common.hcl:2)
  tls://host:port      TCP + TLS: server presents cert/key; client verifies
                       against a CA bundle and may present a client cert
                       (mutual TLS), mirroring the reference's REDIS_TLS +
                       auth dial options (driver_impl.go:60-78)

TCP connections set TCP_NODELAY — the protocol is small length-framed RPCs
and Nagle would add an RTT of latency to every decision.

Resilience (client side): every SUBMIT runs under a per-RPC deadline
(SIDECAR_RPC_DEADLINE, separate from SIDECAR_CONNECT_TIMEOUT), transport
failures get bounded retries with exponential backoff + jitter
(SIDECAR_RETRIES / SIDECAR_RETRY_BACKOFF[_MAX]), a pooled connection dying
mid-RPC triggers ONE free redial after evicting the whole pool (a sidecar
restart stales every pooled socket at once — paying one failed request per
pooled socket would turn one restart into pool_size failures), and a
consecutive-failure circuit breaker (backends/fallback.py:CircuitBreaker)
fails fast while the sidecar is dark so frontends degrade to the
FAILURE_MODE_DENY ladder instead of stacking up dial timeouts. Both ends
consult an optional FaultInjector (testing/faults.py) so chaos tests can
rehearse each of these paths deterministically.
"""

from __future__ import annotations

import contextlib
import logging
import os
import random
import socket
import ssl
import struct
import threading
import time

import numpy as np

from ..limiter.cache import CacheError
from ..tracing import activate, active_span, global_tracer
from ..tracing import journeys
from ..tracing.propagation import decode_textmap, encode_textmap
from ..utils.timeutil import process_time_source
from .fallback import CircuitBreaker

logger = logging.getLogger("ratelimit.sidecar")

MAGIC = 0x524C5343  # 'RLSC'
VERSION = 1
OP_SUBMIT = 1
OP_PING = 2
# warm-standby replication subscribe (persist/replication.py): payload is
# u32 epoch | u64 last_seq; the server acks with one status byte and then
# STREAMS replication frames on this connection until it dies — the one
# op that breaks the request/response rhythm, by design
OP_REPL_SUBSCRIBE = 3
# --- partitioned-cluster admin ops (cluster/) --------------------------
# small request/response RPCs used by the router and the reshard
# coordinator; every one replies u8 status | u32 len | blob (ok) or the
# standard error frame. Owners without a ClusterNode answer errors.
OP_MAP_GET = 4  # empty -> the owner's current PartitionMap JSON
OP_MAP_SET = 5  # u32 len | map JSON -> adopt iff newer epoch
OP_RESHARD_PULL = 6  # u32 lo | u32 hi | u32 route_sets -> rows section
OP_RESHARD_PUSH = 7  # u32 len | pack_table_bytes section -> merge stats
# empty -> the owner's heavy-hitter snapshot JSON (ops/sketch.py; the
# last drained top-K, fingerprints only — frontends hold the key
# witness). Served whether or not the owner is in a cluster, so the
# single-owner debug surface and the router's per-partition aggregation
# (cluster/router.py cluster_snapshot) ride the same verb.
OP_HOTKEYS_GET = 8
# global-quota-federation exchange (cluster/federation.py): payload is
# u32 fence-epoch | u16 name_len | borrower name; the connection then
# becomes a framed request/response exchange (replication frame codec,
# fed kinds) starting with the grantor's full-snapshot resync frame —
# the second op that leaves the request/response rhythm, same shape as
# OP_REPL_SUBSCRIBE. Owners without a FederationCoordinator answer the
# standard error frame (FED_ENABLED=false serves the byte-identical
# pre-federation protocol).
OP_FED_EXCHANGE = 9
# --- chaos-campaign admin ops (testing/faults.py, utils/timeutil.py) ---
# runtime fault/clock reconfiguration on a LIVE owner: the wire twins of
# the debug port's POST /debug/faults and POST /debug/clock, so chaos
# campaigns can flip faults and skew clocks mid-run without a
# FAULT_INJECT reboot. Both reply u8 status | u32 len | blob like the
# cluster admin ops.
OP_FAULTS_SET = 10  # u32 len | JSON {"spec": str, "seed": int?}
#                     -> FaultInjector.describe() JSON; a junk spec
#                     answers the error frame and changes nothing
OP_CLOCK_SET = 11  # u32 len | JSON {"offset_s": float?, "drift_ppm":
#                     float?} -> {"unix_now", "skew"} JSON; {} resets
# header flags (the u16 after op): bit 0 = B3 trace trailer appended,
# bit 1 = lease-ops trailer appended (before the trace trailer),
# bit 2 = u32 epoch trailer appended (after the lease trailer, before the
#         trace trailer) — the split-brain fence: set only by multi-address
#         clients (SIDECAR_ADDRS), so single-address deployments ship
#         byte-identical frames to the pre-replication protocol
# bit 3 = u32 partition-map epoch trailer appended (after the epoch
#         trailer, before the trace trailer) — the cluster routing fence:
#         set only by the partition router (cluster/router.py), so
#         PARTITIONS=1 deployments ship byte-identical legacy frames
FLAG_TRACE = 1
FLAG_LEASE = 2
FLAG_EPOCH = 4
FLAG_MAP = 8

# response status bytes. 0/1 are the original protocol; 2/3 only ever
# answer FLAG_EPOCH frames, and 4 only ever answers FLAG_MAP frames, so
# legacy clients never see them.
STATUS_OK = 0
STATUS_ERROR = 1
STATUS_OK_EPOCH = 2  # u32 epoch | u32 n | counters
STATUS_STALE_EPOCH = 3  # u32 server_epoch — the write was NOT applied
# the frame was routed with a stale/mismatched PartitionMap: the write
# was NOT applied; the body is u32 len | the owner's current map JSON so
# the client re-buckets against it (the Redis Cluster MOVED analog)
STATUS_STALE_MAP = 4
# sanity cap on the trace trailer — B3 TextMap is ~90 bytes
MAX_TRACE_TRAILER = 1024
# sanity cap on the lease trailer (a request carries a handful of grant/
# settle records; 64 KiB is ~4k records)
MAX_LEASE_TRAILER = 1 << 16
# sanity cap on cluster admin bodies (a PartitionMap JSON is ~100 bytes
# per partition; a reshard section is a route range's live rows)
MAX_MAP_BYTES = 1 << 20
MAX_RESHARD_BYTES = 1 << 28


class StaleMapError(CacheError):
    """A SUBMIT was refused with STATUS_STALE_MAP: the owner holds a
    newer (or conflicting) PartitionMap than the one this frame was
    routed with, and the write was NOT applied. Carries the owner's map
    JSON so the router (cluster/router.py) adopts it, re-buckets, and
    resubmits — callers without a router see an ordinary CacheError and
    degrade through the FAILURE_MODE_DENY ladder."""

    def __init__(self, message: str, map_json: bytes):
        super().__init__(message)
        self.map_json = map_json

_HDR = struct.Struct("<IBBH")  # magic, version, op, reserved
_U32 = struct.Struct("<I")

ITEM_ROWS = 6  # fp_lo, fp_hi, hits, limit, divider, jitter

# Hard protocol cap on items per SUBMIT frame. The u32 count is
# client-supplied; without a bound a single bad frame (n=0xFFFFFFFF) would
# make the device-owner process try to buffer ~100 GB. Anything a frontend
# legitimately sends fits well under this (requests are a handful of items;
# the engine's own max_batch is 64k).
MAX_SUBMIT_ITEMS = 1 << 20


def parse_sidecar_address(address: str) -> tuple[str, object]:
    """("unix", path) | ("tcp"|"tls", (host, port)). Anything without a
    tcp:// or tls:// scheme is a unix socket path (backward compatible)."""
    for scheme in ("tcp", "tls"):
        prefix = scheme + "://"
        if address.startswith(prefix):
            hostport = address[len(prefix):]
            host, sep, port = hostport.rpartition(":")
            if not sep or not port.isdigit():
                raise ValueError(
                    f"sidecar address {address!r} must be {scheme}://host:port"
                )
            # [v6::literal]:port — strip the brackets for the socket APIs
            if host.startswith("[") and host.endswith("]"):
                host = host[1:-1]
            return scheme, (host or "127.0.0.1", int(port))
    return "unix", address


def _recv_exact(conn: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("sidecar connection closed")
        buf.extend(chunk)
    return bytes(buf)


def encode_items(items) -> bytes:
    """uint32[6, n] block from a list of _Item (backends/tpu.py)."""
    n = len(items)
    block = np.empty((ITEM_ROWS, n), dtype=np.uint32)
    fp = np.fromiter((it.fp for it in items), dtype=np.uint64, count=n)
    block[0] = (fp & 0xFFFFFFFF).astype(np.uint32)
    block[1] = (fp >> np.uint64(32)).astype(np.uint32)
    block[2] = np.fromiter((it.hits for it in items), np.uint32, n)
    block[3] = np.fromiter((it.limit for it in items), np.uint32, n)
    block[4] = np.fromiter((it.divider for it in items), np.uint32, n)
    block[5] = np.fromiter((it.jitter for it in items), np.uint32, n)
    return _U32.pack(n) + block.tobytes()


def decode_block(payload: bytes) -> np.ndarray:
    """uint32[6, n] wire block view (read-only) from a SUBMIT payload."""
    (n,) = _U32.unpack_from(payload)
    return np.frombuffer(
        payload, dtype=np.uint32, count=ITEM_ROWS * n, offset=_U32.size
    ).reshape(ITEM_ROWS, n)


def decode_items(payload: bytes):
    """Inverse of encode_items; returns a list of _Item."""
    from .tpu import _Item

    block = decode_block(payload)
    n = block.shape[1]
    fp = block[0].astype(np.uint64) | (block[1].astype(np.uint64) << np.uint64(32))
    return [
        _Item(
            fp=int(fp[i]),
            hits=int(block[2, i]),
            limit=int(block[3, i]),
            divider=int(block[4, i]),
            jitter=int(block[5, i]),
        )
        for i in range(n)
    ]


class SlabSidecarServer:
    """The device-owner process. Accepts frontend connections on a unix
    socket or TCP(+TLS) listener; each SUBMIT runs through the engine's
    dispatch loop, which coalesces items from every connected frontend into
    shared launches."""

    def __init__(
        self,
        address: str,
        engine,
        socket_mode: int = 0o600,
        tls_cert: str = "",
        tls_key: str = "",
        tls_ca: str = "",
        fault_injector=None,
        repl=None,
        shm_control_path: str = "",
        cluster=None,
        fed=None,
        time_source=None,
    ):
        """address: unix path, tcp://host:port, or tls://host:port.

        cluster: optional cluster.node.ClusterNode — this owner's
        partition membership. When set, map-stamped SUBMIT frames
        (FLAG_MAP) are fenced against the node's PartitionMap (a stale
        or misrouted frame gets STATUS_STALE_MAP + the current map, the
        write never applied) and the cluster admin ops (OP_MAP_GET/SET,
        OP_RESHARD_PULL/PUSH) are served. None keeps the exact
        pre-cluster behavior — the PARTITIONS=1 rollback arm.

        repl: optional persist.replication.ReplicationCoordinator. When
        set, OP_REPL_SUBSCRIBE connections become its ship loops, a
        standby's first SUBMIT promotes it (epoch bump + reconcile +
        upload, then the write executes against the promoted slab), and
        FLAG_EPOCH frames are epoch-fenced: a frame carrying a NEWER
        epoch than this owner's proves a standby was promoted past it —
        the write is rejected with STATUS_STALE_EPOCH and never executed
        (the split-brain guard). None keeps the exact pre-replication
        behavior.

        fault_injector: optional testing.faults.FaultInjector consulted at
        site 'sidecar.server.submit' before each SUBMIT reaches the engine
        (delay_ms = slow engine, error = error reply, drop = connection
        drop without a response, partial_write = truncated response).

        socket_mode (unix only): filesystem mode for the socket node.
        Default 0o600 restricts to same-UID frontends; pass 0o660 and place
        the socket in a directory owned by a shared group for split-UID
        deployments. Any process that can connect can drive arbitrary
        counter increments, so never leave the default world-connectable
        mode — and for tcp://, bind a private interface or use tls:// with
        tls_ca (mutual TLS: only cert-holding frontends connect).

        tls_cert/tls_key (tls only): server certificate + key, required.
        tls_ca (tls only): when set, frontends must present a client
        certificate signed by this CA."""
        self._engine = engine
        self._faults = fault_injector
        self._repl = repl
        self._cluster = cluster
        # the OP_CLOCK_SET target: the process clock authority unless the
        # boot (or a chaos harness) hands this owner a specific source
        self._time_source = (
            time_source if time_source is not None else process_time_source()
        )
        # fed: optional cluster.federation.FederationCoordinator — when
        # set, OP_FED_EXCHANGE connections become its exchange loops
        # (borrower peers dialing this cluster's share ledger)
        self._fed = fed
        # shm submit rings (SHM_RINGS; backends/shm_ring.py): same-host
        # frontend PROCESSES publish row blocks straight into this
        # engine's dispatch loop through shared-memory rings registered
        # over this control socket — the socket RPC below stays the
        # fallback (lease trailers, cross-host frontends) and the
        # rollback arm. Requires the dispatch loop (windowed mode);
        # engines without one keep the socket-only contract.
        self._shm_control = None
        if shm_control_path:
            loop = getattr(engine, "dispatch_loop", None)
            if loop is None:
                logger.warning(
                    "SHM_RINGS requested but the engine has no dispatch "
                    "loop (direct mode, TPU_BATCH_WINDOW=0): shm "
                    "control socket NOT started, socket RPC only"
                )
            else:
                from .shm_ring import ShmControlServer

                self._shm_control = ShmControlServer(
                    loop, shm_control_path, socket_mode=socket_mode
                )
        self._scheme, target = parse_sidecar_address(address)
        self._path = address
        self._tls_ctx = None
        if self._scheme == "unix":
            try:
                os.unlink(target)
            except FileNotFoundError:
                pass
            self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            # bind-then-chmod (no umask games: umask is process-wide and
            # would leak 0o077 onto files other threads create during the
            # window). Linux checks AF_UNIX connect permissions at connect
            # time against the current node mode, so the pre-chmod window
            # is closed by the chmod landing before listen() accepts.
            self._sock.bind(target)
            os.chmod(target, socket_mode)
        else:
            if self._scheme == "tls":
                if not tls_cert or not tls_key:
                    raise ValueError("tls:// sidecar requires tls_cert + tls_key")
                self._tls_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
                self._tls_ctx.load_cert_chain(tls_cert, tls_key)
                if tls_ca:
                    self._tls_ctx.load_verify_locations(tls_ca)
                    self._tls_ctx.verify_mode = ssl.CERT_REQUIRED
            # family from getaddrinfo so v6 literals/AAAA-only hosts bind
            info = socket.getaddrinfo(
                target[0], target[1], type=socket.SOCK_STREAM
            )[0]
            self._sock = socket.socket(info[0], socket.SOCK_STREAM)
            self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._sock.bind(info[4])
        self._sock.listen(128)
        self._stop = threading.Event()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="sidecar-accept", daemon=True
        )
        self._accept_thread.start()
        logger.info("slab sidecar listening on %s", address)

    @property
    def port(self) -> int:
        """Bound TCP port (tests bind port 0)."""
        return self._sock.getsockname()[1]

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True
            ).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            net = self._scheme in ("tcp", "tls")
            if net:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            if self._tls_ctx is not None:
                # handshake here, per-connection thread — a client stalling
                # mid-handshake must not block the accept loop. The 10s
                # timeout bounds the PRE-authentication window: an
                # unauthenticated peer must not pin this thread/fd forever
                # (slowloris) on a network-exposed listener.
                conn.settimeout(10.0)
                conn = self._tls_ctx.wrap_socket(conn, server_side=True)
                conn.settimeout(None)
            with conn:
                while not self._stop.is_set():
                    # idle waits are unbounded (frontends pool connections
                    # between requests) but once a frame STARTS it must
                    # finish promptly — a half-sent frame holds the thread
                    if net:
                        conn.settimeout(None)
                    hdr = _recv_exact(conn, _HDR.size)
                    if net:
                        conn.settimeout(30.0)
                    magic, version, op, hdr_flags = _HDR.unpack(hdr)
                    if magic != MAGIC or version != VERSION:
                        conn.sendall(self._error(f"bad header {hdr!r}"))
                        return
                    if op == OP_PING:
                        conn.sendall(b"\x00")
                        continue
                    if op == OP_REPL_SUBSCRIBE:
                        # u32 epoch | u64 last_seq (diagnostic; the ship
                        # loop always starts with a full snapshot)
                        _recv_exact(conn, 12)
                        if self._repl is None:
                            conn.sendall(
                                self._error("replication not configured")
                            )
                            return
                        if net:
                            conn.settimeout(None)
                        # the connection becomes this subscriber's ship
                        # loop; it never returns to request/response
                        self._repl.serve_subscriber(conn)
                        return
                    if op == OP_FED_EXCHANGE:
                        if self._fed is None:
                            conn.sendall(
                                self._error("federation not configured")
                            )
                            return
                        if net:
                            conn.settimeout(None)
                        # the connection becomes this borrower's exchange
                        # loop; it never returns to request/response
                        self._fed.serve_exchange(conn)
                        return
                    if op in (
                        OP_MAP_GET,
                        OP_MAP_SET,
                        OP_RESHARD_PULL,
                        OP_RESHARD_PUSH,
                        OP_HOTKEYS_GET,
                        OP_FAULTS_SET,
                        OP_CLOCK_SET,
                    ):
                        if not self._serve_cluster_op(conn, op):
                            return
                        continue
                    if op != OP_SUBMIT:
                        conn.sendall(self._error(f"bad op {op}"))
                        return
                    n_raw = _recv_exact(conn, _U32.size)
                    (n,) = _U32.unpack(n_raw)
                    if n > MAX_SUBMIT_ITEMS:
                        # reject BEFORE buffering the payload
                        conn.sendall(
                            self._error(
                                f"submit count {n} exceeds cap {MAX_SUBMIT_ITEMS}"
                            )
                        )
                        return
                    payload = n_raw + _recv_exact(conn, ITEM_ROWS * n * 4)
                    lease_blob = None
                    if hdr_flags & FLAG_LEASE:
                        # lease-ops trailer: read BEFORE fault handling so
                        # the frame stays wire-coherent; decoded (and
                        # validated) only after the engine answered
                        (blob_len,) = _U32.unpack(
                            _recv_exact(conn, _U32.size)
                        )
                        if blob_len > MAX_LEASE_TRAILER:
                            conn.sendall(
                                self._error(
                                    f"lease trailer {blob_len} exceeds "
                                    f"cap {MAX_LEASE_TRAILER}"
                                )
                            )
                            return
                        lease_blob = _recv_exact(conn, blob_len)
                    frame_epoch = None
                    if hdr_flags & FLAG_EPOCH:
                        # epoch fence trailer (fixed u32): read before any
                        # fault handling so the frame stays wire-coherent
                        (frame_epoch,) = _U32.unpack(
                            _recv_exact(conn, _U32.size)
                        )
                    frame_map_epoch = None
                    if hdr_flags & FLAG_MAP:
                        # partition-map fence trailer (fixed u32): same
                        # wire-coherence rule as the epoch trailer
                        (frame_map_epoch,) = _U32.unpack(
                            _recv_exact(conn, _U32.size)
                        )
                    wire_ctx = None
                    if hdr_flags & FLAG_TRACE:
                        # B3 trace trailer: read it BEFORE any fault
                        # handling so the frame stays wire-coherent; a
                        # malformed trailer decodes to None and the
                        # request proceeds untraced, never fails
                        (blob_len,) = _U32.unpack(
                            _recv_exact(conn, _U32.size)
                        )
                        if blob_len > MAX_TRACE_TRAILER:
                            conn.sendall(
                                self._error(
                                    f"trace trailer {blob_len} exceeds "
                                    f"cap {MAX_TRACE_TRAILER}"
                                )
                            )
                            return
                        wire_ctx = decode_textmap(
                            _recv_exact(conn, blob_len)
                        )
                    if self._faults is not None:
                        # chaos hook: the frame is fully read (so the
                        # client's framing stays coherent), the response is
                        # where the fault lands
                        action = self._faults.fire("sidecar.server.submit")
                        if action == "drop":
                            return  # connection dies without a response
                        if action == "error":
                            conn.sendall(self._error("injected fault"))
                            continue
                        if action == "partial_write":
                            # status byte without the counts, then close —
                            # the client sees a mid-frame connection loss
                            conn.sendall(b"\x00")
                            return
                    if self._cluster is not None:
                        # the cluster routing fence: a frame routed with
                        # a stale map, or carrying rows this partition
                        # does not own, is answered with the CURRENT map
                        # and never applied — checked BEFORE the repl
                        # promote-on-write so a misrouted frame cannot
                        # promote a standby it was never meant for
                        stale_map = self._cluster.check_block(
                            frame_map_epoch, decode_block(payload)
                        )
                        if stale_map is not None:
                            conn.sendall(
                                bytes([STATUS_STALE_MAP])
                                + _U32.pack(len(stale_map))
                                + stale_map
                            )
                            continue
                    if self._repl is not None:
                        # a write reaching a standby IS the failover
                        # signal: promote (epoch bump + reconcile +
                        # upload) before executing it. Idempotent and
                        # thread-safe — concurrent first writes all wait
                        # on the one transition.
                        if self._repl.is_standby:
                            self._repl.promote(
                                reason="client write reached standby"
                            )
                        if (
                            frame_epoch is not None
                            and frame_epoch > self._repl.epoch
                        ):
                            # the split-brain guard: the client has seen a
                            # newer epoch than this owner serves — this is
                            # a resurrected stale primary and the write
                            # must NOT touch its slab
                            self._repl.note_stale_write(frame_epoch)
                            conn.sendall(
                                bytes([STATUS_STALE_EPOCH])
                                + _U32.pack(self._repl.epoch)
                            )
                            continue
                    # server span parented by the frontend's wire context
                    # (B3 over the sidecar wire), activated so the
                    # dispatch loop's ring ctx and batch-span links see
                    # it; plus the device-owner-side journey
                    tracer = global_tracer()
                    server_span = None
                    if wire_ctx is not None and tracer.enabled:
                        server_span = tracer.start_span(
                            "sidecar.submit_rows",
                            child_of=wire_ctx,
                            tags={
                                "span.kind": "server",
                                "component": "sidecar",
                                "batch_items": n,
                            },
                        )
                    recorder = journeys.global_recorder()
                    journey = None
                    if recorder is not None:
                        journey = recorder.begin(
                            "sidecar.submit",
                            trace_id=(
                                wire_ctx.trace_id if wire_ctx else 0
                            ),
                            span_id=wire_ctx.span_id if wire_ctx else 0,
                        )
                    t_req_ns = time.monotonic_ns()
                    try:
                        scope_cm = (
                            activate(server_span)
                            if server_span is not None
                            else contextlib.nullcontext()
                        )
                        with scope_cm:
                            if getattr(self._engine, "block_mode", False):
                                # block-native engine: the wire block IS
                                # the device input (minus bucket pad +
                                # scalar row) — no per-item Python objects
                                # anywhere on the aggregation path
                                afters = self._engine.submit_block(
                                    decode_block(payload)
                                )
                            else:
                                afters = self._engine.submit(
                                    decode_items(payload)
                                )
                        out = np.asarray(afters, dtype=np.uint32)
                        if lease_blob is not None:
                            # register the frame's lease liabilities with
                            # the launch's post-increment counters as
                            # floors; a malformed trailer is an error
                            # reply, never a crash (the increments are
                            # already applied — same posture as any
                            # post-launch application error)
                            self._apply_lease_blob(lease_blob, payload, out)
                        # close the span/journey BEFORE the reply hits the
                        # wire: once the client sees the response, this
                        # request's server-side trace must already exist
                        if server_span is not None:
                            server_span.finish()
                        if journey is not None:
                            recorder.finish(
                                journey,
                                (time.monotonic_ns() - t_req_ns) / 1e6,
                            )
                        if frame_epoch is not None:
                            # epoch-flagged frames get the epoch-carrying
                            # reply so failed-over clients learn the
                            # promoted epoch; repl-less owners answer 0
                            # (clients ignore it)
                            my_epoch = (
                                self._repl.epoch
                                if self._repl is not None
                                else 0
                            )
                            conn.sendall(
                                bytes([STATUS_OK_EPOCH])
                                + _U32.pack(my_epoch)
                                + _U32.pack(len(out))
                                + out.tobytes()
                            )
                        else:
                            conn.sendall(
                                b"\x00" + _U32.pack(len(out)) + out.tobytes()
                            )
                    except Exception as e:  # noqa: BLE001 - surface to client
                        if server_span is not None:
                            server_span.set_error(e)
                            server_span.finish()
                        if journey is not None:
                            recorder.finish(
                                journey,
                                (time.monotonic_ns() - t_req_ns) / 1e6,
                                flags=(journeys.FLAG_FAULT,),
                            )
                        if self._stop.is_set():
                            # shutting down: let the connection die instead
                            # of answering with an error reply. A transport
                            # failure is safely retryable (the closed
                            # engine never executed the batch), so a
                            # restarting sidecar costs clients a redial
                            # instead of a failed request; an error reply
                            # is never retried.
                            return
                        logger.exception("sidecar submit failed")
                        conn.sendall(self._error(str(e)))
        except (ConnectionError, OSError):
            return  # frontend went away

    def _apply_lease_blob(
        self, lease_blob: bytes, payload: bytes, out: np.ndarray
    ) -> None:
        """Decode one frame's lease trailer and register it against the
        engine's liability registry (engines without one ignore lease
        traffic — exotic test engines)."""
        apply_ops = getattr(self._engine, "apply_lease_ops", None)
        if apply_ops is None:
            return
        from .lease import decode_lease_ops

        apply_ops(decode_block(payload), out, decode_lease_ops(lease_blob))

    def _serve_cluster_op(self, conn: socket.socket, op: int) -> bool:
        """One cluster admin RPC (OP_MAP_GET/SET, OP_RESHARD_PULL/PUSH).
        Every op replies u8 status | u32 len | blob; returns False when
        the connection should close (protocol violation)."""
        import json as _json

        if op == OP_RESHARD_PULL:
            lo, hi, route_sets = struct.unpack("<III", _recv_exact(conn, 12))
        elif op in (OP_MAP_SET, OP_RESHARD_PUSH, OP_FAULTS_SET, OP_CLOCK_SET):
            (blob_len,) = _U32.unpack(_recv_exact(conn, _U32.size))
            cap = MAX_MAP_BYTES if op != OP_RESHARD_PUSH else MAX_RESHARD_BYTES
            if blob_len > cap:
                conn.sendall(
                    self._error(f"cluster op body {blob_len} exceeds cap {cap}")
                )
                return False
            body = _recv_exact(conn, blob_len)
        if self._cluster is None and op in (OP_MAP_GET, OP_MAP_SET):
            conn.sendall(self._error("cluster not configured"))
            return True
        try:
            if op == OP_FAULTS_SET:
                out = self._serve_faults_set(body)
            elif op == OP_CLOCK_SET:
                out = self._serve_clock_set(body)
            elif op == OP_HOTKEYS_GET:
                snap_fn = getattr(self._engine, "hotkeys_snapshot", None)
                snap = (
                    snap_fn()
                    if snap_fn is not None
                    else {"enabled": False, "k": 0, "lanes": 0,
                          "drains": 0, "top": []}
                )
                out = _json.dumps(snap).encode()
            elif op == OP_MAP_GET:
                out = self._cluster.pmap.to_json_bytes()
            elif op == OP_MAP_SET:
                adopted = self._cluster.adopt_json(body)
                out = _json.dumps(
                    {"adopted": adopted, "epoch": self._cluster.epoch}
                ).encode()
            elif op == OP_RESHARD_PULL:
                from ..persist.snapshot import pack_table_bytes

                rows = self._engine.export_route_range(lo, hi, route_sets)
                engine_ts = getattr(self._engine, "_time_source", None)
                snap_now = (
                    engine_ts.unix_now()
                    if engine_ts is not None
                    else process_time_source().unix_now()
                )
                out = pack_table_bytes(
                    rows, snap_now, ways=getattr(self._engine, "ways", 0)
                )
            else:  # OP_RESHARD_PUSH
                from ..persist.snapshot import unpack_table_bytes

                _hdr, rows, _off = unpack_table_bytes(
                    body, what="<reshard push>"
                )
                out = _json.dumps(self._engine.merge_rows(rows)).encode()
        except Exception as e:  # noqa: BLE001 - surface to the coordinator
            logger.exception("cluster op %d failed", op)
            conn.sendall(self._error(str(e)))
            return True
        conn.sendall(b"\x00" + _U32.pack(len(out)) + out)
        return True

    def _serve_faults_set(self, body: bytes) -> bytes:
        """OP_FAULTS_SET: replace the owner's live fault rule set. The
        injector is the one the engine/snapshotter/repl/fed already hold
        (cmd/sidecar_cmd.py builds it unconditionally); a junk spec
        raises, which the cluster-op wrapper answers as the standard
        error frame — fail-loud, nothing changed."""
        import json as _json

        from ..testing.faults import parse_fault_spec

        if self._faults is None:
            raise ValueError("fault injector not configured on this owner")
        doc = _json.loads(body.decode("utf-8")) if body else {}
        rules = parse_fault_spec(str(doc.get("spec", "")))
        seed = doc.get("seed")
        self._faults.configure(
            rules, seed=None if seed is None else int(seed)
        )
        return _json.dumps(self._faults.describe()).encode()

    def _serve_clock_set(self, body: bytes) -> bytes:
        """OP_CLOCK_SET: step/drift this owner's clock authority — the
        chaos clock-skew nemesis against a live process. Applies to the
        server's time source (the process singleton in a real boot);
        an un-skewable source answers the error frame."""
        import json as _json

        ts = self._time_source
        set_skew = getattr(ts, "set_skew", None)
        if set_skew is None:
            raise ValueError("owner time source is not skewable")
        doc = _json.loads(body.decode("utf-8")) if body else {}
        set_skew(
            offset_s=float(doc.get("offset_s", 0.0)),
            drift_ppm=float(doc.get("drift_ppm", 0.0)),
        )
        return _json.dumps(
            {"unix_now": ts.unix_now(), "skew": ts.skew()}
        ).encode()

    @staticmethod
    def _error(message: str) -> bytes:
        raw = message.encode()
        return b"\x01" + _U32.pack(len(raw)) + raw

    def close(self) -> None:
        self._stop.set()
        if self._shm_control is not None:
            self._shm_control.close()
        # shutdown BEFORE close: a thread blocked in accept() does not
        # reliably wake on close() alone (Linux), which leaves the kernel
        # socket held and a restart on the same port failing EADDRINUSE.
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        self._accept_thread.join(5.0)
        if self._scheme == "unix":
            try:
                os.unlink(self._path)
            except OSError:
                pass
        self._engine.close()


class SidecarEngineClient:
    """Frontend-side device driver: same submit/flush/close verbs as
    SlabDeviceEngine, executed by the sidecar process over the socket.
    Connections are pooled so frontend threads overlap their RPCs — the
    sidecar's batcher turns that concurrency into bigger launches."""

    def __init__(
        self,
        address,
        pool_size: int = 8,
        timeout: float = 30.0,
        tls_ca: str = "",
        tls_cert: str = "",
        tls_key: str = "",
        tls_server_name: str = "",
        scope=None,
        connect_timeout: float | None = None,
        rpc_deadline: float | None = None,
        retries: int = 2,
        retry_backoff: float = 0.01,
        retry_backoff_max: float = 0.25,
        breaker_threshold: int = 5,
        breaker_reset: float = 5.0,
        fault_injector=None,
        sleep=time.sleep,
        shm_control_path: str = "",
        shm_ring_rows: int = 4096,
        map_epoch_fn=None,
    ):
        """address: unix path, tcp://host:port, or tls://host:port — or a
        LIST of them (equivalently one comma-separated string: the
        SIDECAR_ADDRS form). The first entry is the primary; the rest are
        warm standbys in failover order. With more than one address the
        client becomes epoch-aware: every SUBMIT carries a FLAG_EPOCH
        trailer with the highest epoch it has seen, the breaker opening
        (or an address's retry budget exhausting, or a stale-epoch reply)
        fails the client over to the next address — whose first write
        promotes it — and a resurrected stale primary answering
        STATUS_STALE_EPOCH is failed away from instead of trusted. A
        single address keeps the wire format and behavior byte-identical
        to the pre-replication client (the rollback arm, pinned by test).
        tls_ca: CA bundle the server cert must chain to (defaults to the
        system store when empty). tls_cert/tls_key: client certificate for
        mutual TLS. tls_server_name: SNI/hostname override when the cert CN
        doesn't match the dialed host (the reference's equivalent knob:
        tls dial options, driver_impl.go:60-78).

        scope: optional stats Scope; records <scope>.sidecar.rpc_ms — the
        frontend-side SUBMIT round trip (socket + the sidecar's own
        batcher/device stages) — plus the resilience stats:
        <scope>.sidecar.{retry,redial,breaker_open} counters and the
        <scope>.sidecar.breaker_state gauge (0 closed / 1 half-open /
        2 open).

        connect_timeout / rpc_deadline: dial timeout vs per-RPC deadline
        (send + full response read). Both default to the legacy `timeout`
        so existing callers keep one-knob behavior; SIDECAR_CONNECT_TIMEOUT
        and SIDECAR_RPC_DEADLINE set them separately in production.

        retries / retry_backoff / retry_backoff_max: bounded retries for
        TRANSPORT-level failures (dial errors, resets, deadline expiry)
        with exponential backoff + full jitter. Error REPLIES from the
        sidecar are application-level and never retried (the engine may
        have applied the increment). Independent of the retry budget, a
        POOLED connection that dies mid-RPC gets one free redial after
        evicting the whole pool: a sidecar restart stales every pooled
        socket at once, and the redial makes that restart cost zero failed
        requests instead of pool_size.

        breaker_threshold / breaker_reset: consecutive transport failures
        that open the circuit, and the open->half-open probe delay.
        threshold 0 disables the breaker. While open, submit() fails fast
        with CacheError (no dialing) so the service's FAILURE_MODE_DENY
        ladder answers instead of every request eating a timeout.

        fault_injector: optional testing.faults.FaultInjector; consulted at
        'sidecar.dial' per dial and 'sidecar.submit' per SUBMIT attempt.

        shm_control_path (SHM_RINGS; backends/shm_ring.py): when set and
        this is a SINGLE-address client, plain row-block submits publish
        through a shared-memory ring straight into the device owner's
        dispatch loop instead of the socket RPC — the per-request hot
        path crosses no sockets. Frames that need wire trailers (lease
        ops) and multi-address epoch-fenced clients stay on the socket
        path, and any shm TRANSPORT failure falls back to the socket RPC
        per call (counted in <scope>.sidecar.shm_fallback) so a dying
        owner degrades through the existing retry/breaker/failover
        ladder, never a new one.

        map_epoch_fn: optional zero-arg callable returning the epoch of
        the PartitionMap this client's frames were routed with
        (cluster/router.py sets it on each per-partition client). When
        set, every SUBMIT carries a FLAG_MAP trailer and a
        STATUS_STALE_MAP reply raises StaleMapError (carrying the
        owner's current map) instead of retrying — re-bucketing is the
        router's job, not the transport's. None (the default) ships
        byte-identical pre-cluster frames."""
        self._map_epoch_fn = map_epoch_fn
        self._h_rpc = None
        self._h_shm = None
        self._c_retry = self._c_redial = self._c_breaker_open = None
        self._c_failover = self._c_shm_fallback = None
        self._g_breaker_state = self._g_active_backend = None
        self._g_shm_active = None
        if scope is not None:
            sc = scope.scope("sidecar")
            self._h_rpc = sc.histogram("rpc_ms")
            self._h_shm = sc.histogram("shm_ms")
            self._c_retry = sc.counter("retry")
            self._c_redial = sc.counter("redial")
            self._c_breaker_open = sc.counter("breaker_open")
            self._c_failover = sc.counter("failover")
            self._c_shm_fallback = sc.counter("shm_fallback")
            self._g_breaker_state = sc.gauge("breaker_state")
            self._g_breaker_state.set(0)
            self._g_active_backend = sc.gauge("active_backend")
            self._g_active_backend.set(0)
            self._g_shm_active = sc.gauge("shm_active")
            self._g_shm_active.set(0)
        if isinstance(address, str):
            addrs = [a.strip() for a in address.split(",") if a.strip()]
        else:
            addrs = [str(a) for a in address]
        if not addrs:
            raise ValueError("sidecar address list is empty")
        self._addrs = addrs
        self._addr_lock = threading.Lock()
        self._active = 0
        # epoch awareness exists ONLY with standbys to fail over to; a
        # single-address client ships the exact legacy frame (flags bit 2
        # clear, no trailer) — the byte-identical rollback arm
        self._epoch_aware = len(addrs) > 1
        self._epoch_known = 0
        self._path = addrs[0]
        self._scheme, self._target = parse_sidecar_address(addrs[0])
        self._timeout = timeout
        self._connect_timeout = (
            timeout if connect_timeout is None else float(connect_timeout)
        )
        self._rpc_deadline = (
            timeout if rpc_deadline is None else float(rpc_deadline)
        )
        self._retries = max(0, int(retries))
        self._retry_backoff = max(0.0, float(retry_backoff))
        self._retry_backoff_max = max(
            self._retry_backoff, float(retry_backoff_max)
        )
        self._breaker_reset = float(breaker_reset)
        self._breaker = CircuitBreaker(
            breaker_threshold,
            breaker_reset,
            on_transition=self._on_breaker_transition,
        )
        self._faults = fault_injector
        self._sleep = sleep
        # full jitter over the exponential backoff: concurrent frontend
        # threads retrying a restarted sidecar must not re-dial in lockstep
        self._jitter = random.Random()
        self._tls_ctx = None
        self._tls_server_name = tls_server_name
        if self._scheme == "tls":
            self._tls_ctx = ssl.create_default_context(
                cafile=tls_ca or None
            )
            if tls_cert and tls_key:
                self._tls_ctx.load_cert_chain(tls_cert, tls_key)
        self._pool: list[socket.socket] = []
        self._pool_lock = threading.Lock()
        self._pool_size = pool_size
        self._closed = False
        # fail fast like the reference's startup PING (driver_impl.go:124-128).
        # The read is part of the check: under TLS 1.3 a rejected client
        # certificate only surfaces on the first read after the handshake.
        # Deliberately not retried and not breaker-counted — a frontend
        # booting against a dark sidecar should fail its boot loudly.
        # With SIDECAR_ADDRS the ping walks the failover order instead:
        # a dark primary with a live standby is exactly the redundancy
        # story, not a boot failure.
        last_err: CacheError | None = None
        for _ in range(len(self._addrs)):
            try:
                conn = self._dial()
                try:
                    conn.sendall(_HDR.pack(MAGIC, VERSION, OP_PING, 0))
                    ok = _recv_exact(conn, 1) == b"\x00"
                except (OSError, ConnectionError) as e:
                    conn.close()
                    raise CacheError(
                        f"sidecar ping failed on {self._path}: {e}"
                    ) from e
                if not ok:
                    conn.close()
                    raise CacheError(f"sidecar ping failed on {self._path}")
                self._release(conn)
                last_err = None
                break
            except CacheError as e:
                last_err = e
                if not self._epoch_aware:
                    raise
                self._failover(cause=f"boot ping failed: {e}")
        if last_err is not None:
            raise last_err
        # shm submit rings — attached AFTER the boot ping proved the
        # owner up. Best-effort: a missing control socket (owner built
        # without SHM_RINGS, older owner) logs once and leaves the
        # socket RPC path as the only path. Multi-address clients never
        # attach: shm frames carry no epoch fence, so the failover
        # story stays on the wire where it is enforced.
        self._shm = None
        if shm_control_path and not self._epoch_aware:
            try:
                from .shm_ring import ShmRingClient, ShmUnavailable

                try:
                    self._shm = ShmRingClient(
                        shm_control_path,
                        arena_rows=int(shm_ring_rows),
                        submit_timeout=self._rpc_deadline,
                        fault_injector=fault_injector,
                    )
                    if self._g_shm_active is not None:
                        self._g_shm_active.set(1)
                    logger.info(
                        "shm submit rings active via %s", shm_control_path
                    )
                except ShmUnavailable as e:
                    # an owner without SHM_RINGS simply has no control
                    # socket — expected, not alarming
                    logger.info(
                        "shm submit rings not offered by the owner (%s): "
                        "socket RPC only",
                        e,
                    )
            except Exception as e:  # noqa: BLE001 - strictly optional
                logger.warning(
                    "shm submit rings unavailable (%s): socket RPC only", e
                )

    def _on_breaker_transition(self, prev: str, state: str) -> None:
        if self._g_breaker_state is not None:
            self._g_breaker_state.set(CircuitBreaker.STATE_CODES[state])
        if state == CircuitBreaker.OPEN:
            if self._c_breaker_open is not None:
                self._c_breaker_open.inc()
            logger.warning(
                "sidecar circuit OPEN on %s: failing fast for %.3fs",
                self._path,
                self._breaker_reset,
            )
        elif state == CircuitBreaker.CLOSED and prev != CircuitBreaker.CLOSED:
            logger.info("sidecar circuit closed on %s", self._path)

    @property
    def breaker(self) -> CircuitBreaker:
        """The transport circuit breaker (tests/debug observability)."""
        return self._breaker

    @property
    def active_address(self) -> str:
        """The address currently being written to (tests/debug)."""
        with self._addr_lock:
            return self._addrs[self._active]

    def failover_reason(self) -> str | None:
        """HealthChecker degraded-probe contract: a reason string while
        this frontend serves from a non-primary address — the cluster is
        one more failure from the degradation ladder, which operators
        should see on /healthcheck while it keeps serving."""
        with self._addr_lock:
            if self._active == 0:
                return None
            return (
                f"sidecar.failover: serving from standby "
                f"{self._addrs[self._active]} (primary {self._addrs[0]} "
                f"unreachable or stale)"
            )

    def _failover(self, cause: str, span=None) -> str:
        """Rotate to the next address in SIDECAR_ADDRS order: evict every
        pooled connection (they point at the dead/stale owner), reset the
        breaker for the new target, and mark the moment on the active
        trace span and journey (FLAG_FAILOVER) so /debug/journeys retains
        the requests that rode a failover. Returns the new address."""
        with self._addr_lock:
            self._active = (self._active + 1) % len(self._addrs)
            self._path = self._addrs[self._active]
            self._scheme, self._target = parse_sidecar_address(self._path)
            new_addr = self._path
            active = self._active
        self._evict_pool()
        # a fresh target deserves a closed breaker: its failure streak
        # belongs to the address we just left
        self._breaker.record_success()
        if self._c_failover is not None:
            self._c_failover.inc()
        if self._g_active_backend is not None:
            self._g_active_backend.set(active)
        logger.warning(
            "sidecar FAILOVER to %s (backend %d of %d): %s",
            new_addr,
            active + 1,
            len(self._addrs),
            cause,
        )
        target_span = span if span is not None else active_span()
        if target_span is not None:
            target_span.log_kv(
                event="sidecar.failover", to=new_addr, cause=cause
            )
        journeys.note_flag(journeys.FLAG_FAILOVER)
        return new_addr

    def _dial(self) -> socket.socket:
        with self._addr_lock:
            scheme, target, path = self._scheme, self._target, self._path
        if self._faults is not None:
            action = self._faults.fire("sidecar.dial")
            if action is not None:
                raise CacheError(
                    f"cannot reach slab sidecar at {path}: "
                    f"injected fault: {action}"
                )
        if scheme == "unix":
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.settimeout(self._connect_timeout)
            try:
                conn.connect(target)
            except OSError as e:
                conn.close()
                raise CacheError(
                    f"cannot reach slab sidecar at {path}: {e}"
                )
            conn.settimeout(self._rpc_deadline)
            return conn
        try:
            conn = socket.create_connection(
                target, timeout=self._connect_timeout
            )
        except OSError as e:
            raise CacheError(f"cannot reach slab sidecar at {path}: {e}")
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            if self._tls_ctx is not None:
                conn = self._tls_ctx.wrap_socket(
                    conn,
                    server_hostname=self._tls_server_name or target[0],
                )
        except OSError as e:
            conn.close()
            raise CacheError(f"sidecar TLS handshake failed on {path}: {e}")
        conn.settimeout(self._rpc_deadline)
        return conn

    def _acquire(self) -> tuple[socket.socket, bool]:
        """(connection, came_from_pool). The pooled flag drives the free
        redial: only an IDLE-STALE socket qualifies (a fresh dial that dies
        mid-RPC is a live failure, not a restart artifact)."""
        with self._pool_lock:
            if self._pool:
                return self._pool.pop(), True
        return self._dial(), False

    def _release(self, conn: socket.socket) -> None:
        with self._pool_lock:
            if not self._closed and len(self._pool) < self._pool_size:
                self._pool.append(conn)
                return
        conn.close()

    def _evict_pool(self) -> None:
        """Close every pooled connection. Called on the first detected
        stale-socket death (ECONNRESET/EPIPE on a pooled conn): a sidecar
        restart stales the WHOLE pool, and evicting it all at once keeps
        one detected restart from becoming pool_size serial failures."""
        with self._pool_lock:
            stale, self._pool = self._pool, []
        for conn in stale:
            conn.close()

    def _backoff(self, attempt: int) -> float:
        """Exponential backoff with full jitter for retry `attempt` (1-based)."""
        ceiling = min(
            self._retry_backoff_max,
            self._retry_backoff * (2 ** (attempt - 1)),
        )
        return self._jitter.uniform(0.0, ceiling)

    def submit(self, items) -> list[int]:
        if not items:
            return []
        return self._submit_payload(encode_items(items)).tolist()

    def submit_rows(
        self, block: np.ndarray, lease_ops=None
    ) -> np.ndarray:
        """Zero-object verb: the uint32[6, n] row block IS the wire layout,
        so the request frame is one header + one buffer copy — no per-item
        encode at all. lease_ops (backends/lease.py LeaseOps) rides the
        frame as the FLAG_LEASE trailer: the grants' INCRBY is already in
        the hits column, the trailer is the liability bookkeeping the
        device owner registers after the launch."""
        n = block.shape[1]
        if n == 0:
            return np.empty(0, dtype=np.uint32)
        has_lease = lease_ops is not None and (
            lease_ops.grants or lease_ops.settles
        )
        # shm fast path: plain frames publish straight into the owner's
        # dispatch loop. Lease-carrying frames need the wire trailer and
        # ride the socket; shm transport death falls back per call and
        # the socket ladder (retry/breaker) takes it from there.
        shm = self._shm
        if shm is not None and not has_lease and not shm.dead:
            from .shm_ring import ShmUnavailable

            t0 = time.perf_counter() if self._h_shm is not None else 0.0
            try:
                out = shm.submit(block)
                if self._h_shm is not None:
                    self._h_shm.record((time.perf_counter() - t0) * 1e3)
                return out
            except ShmUnavailable as e:
                if self._c_shm_fallback is not None:
                    self._c_shm_fallback.inc()
                if self._g_shm_active is not None and shm.dead:
                    self._g_shm_active.set(0)
                logger.warning(
                    "shm submit unavailable (%s): falling back to socket", e
                )
        payload = _U32.pack(n) + np.ascontiguousarray(
            block, dtype=np.uint32
        ).tobytes()
        extra_flags = 0
        if has_lease:
            from .lease import encode_lease_ops

            payload += encode_lease_ops(lease_ops)
            extra_flags = FLAG_LEASE
        return self._submit_payload(payload, extra_flags)

    def _submit_payload(
        self, payload: bytes, extra_flags: int = 0
    ) -> np.ndarray:
        t0 = time.perf_counter() if self._h_rpc is not None else 0.0
        if not self._breaker.allow():
            # the PR-2 breaker opening on the primary IS the failover
            # trigger: with a standby configured, switch instead of
            # failing fast — its first write will promote it
            if self._epoch_aware:
                self._failover(cause="circuit breaker open")
            else:
                raise CacheError(
                    f"sidecar circuit open on {self._path}: failing fast"
                )
        # B3 over the sidecar wire: a client child span whose injected
        # context rides the frame as a TextMap trailer, so the device-owner
        # process's spans parent into this request's trace. Retries and
        # redials log onto this same span — one trace per request, however
        # many transport attempts it took. Untraced requests build nothing
        # and ship zero extra bytes.
        parent = active_span()
        rpc_span = None
        hdr_flags = extra_flags
        epoch_trailer = b""
        if self._epoch_aware:
            # the split-brain fence: carry the highest epoch this client
            # has seen, so a resurrected stale primary rejects the write
            # instead of double-serving old counters. Single-address
            # clients never set this bit — byte-identical legacy frames.
            hdr_flags |= FLAG_EPOCH
            epoch_trailer = _U32.pack(self._epoch_known)
        map_trailer = b""
        if self._map_epoch_fn is not None:
            # the cluster routing fence: which map these rows were
            # bucketed with — a stale one gets the new map back, never a
            # silently misrouted write
            hdr_flags |= FLAG_MAP
            map_trailer = _U32.pack(int(self._map_epoch_fn()))
        trailer = b""
        if parent is not None and parent.tracer is not None:
            rpc_span = parent.tracer.start_span(
                "sidecar.submit",
                child_of=parent,
                tags={"span.kind": "client", "component": "sidecar"},
            )
            raw = encode_textmap(rpc_span.context)
            trailer = _U32.pack(len(raw)) + raw
            hdr_flags |= FLAG_TRACE
        request = (
            _HDR.pack(MAGIC, VERSION, OP_SUBMIT, hdr_flags)
            + payload
            + epoch_trailer
            + map_trailer
            + trailer
        )
        try:
            return self._submit_attempts(request, rpc_span, t0)
        except BaseException as e:
            if rpc_span is not None:
                rpc_span.set_error(e)
            raise
        finally:
            if rpc_span is not None:
                rpc_span.finish()

    def _submit_attempts(self, request: bytes, rpc_span, t0: float) -> np.ndarray:
        attempt = 0
        redialed = False
        # bounded address rotation per call: once an address's retry
        # budget exhausts (or it answers stale-epoch), the request moves
        # to the next SIDECAR_ADDRS entry instead of failing — a primary
        # crash with a live standby costs zero failed requests. At most
        # one full pass over the standby list, then the error surfaces to
        # the FAILURE_MODE_DENY ladder like any exhausted transport.
        failovers = 0

        def fail_over_or_raise(cause: str) -> bool:
            nonlocal failovers, attempt, redialed
            if not self._epoch_aware or failovers >= len(self._addrs) - 1:
                return False
            failovers += 1
            attempt = 0
            redialed = False
            self._failover(cause, span=rpc_span)
            return True

        while True:
            try:
                conn, pooled = self._acquire()
            except CacheError as e:
                # dial failure: transport-level, retried under the budget
                attempt += 1
                if attempt > self._retries:
                    self._breaker.record_failure()
                    if fail_over_or_raise(f"dial failed: {e}"):
                        continue
                    raise
                if self._c_retry is not None:
                    self._c_retry.inc()
                if rpc_span is not None:
                    rpc_span.log_kv(
                        event="sidecar.retry",
                        attempt=attempt,
                        cause="dial",
                        error=str(e),
                    )
                self._sleep(self._backoff(attempt))
                continue
            stale_epoch = None
            try:
                if self._faults is not None:
                    action = self._faults.fire("sidecar.submit")
                    if action is not None:
                        if rpc_span is not None:
                            rpc_span.log_kv(
                                event="fault",
                                site="sidecar.submit",
                                kind=action,
                            )
                        raise ConnectionError(f"injected fault: {action}")
                conn.sendall(request)
                status = _recv_exact(conn, 1)
                if status == b"\x01":
                    (ln,) = _U32.unpack(_recv_exact(conn, _U32.size))
                    message = _recv_exact(conn, ln).decode()
                    self._release(conn)
                    # an error REPLY rode a healthy transport: application-
                    # level, never retried (the increment may have been
                    # applied), resets the breaker's failure streak
                    self._breaker.record_success()
                    raise CacheError(f"sidecar error: {message}")
                if status == bytes([STATUS_STALE_MAP]):
                    # the owner refused the ROUTING, not the transport:
                    # the reply carries its current map; re-bucketing is
                    # the router's job, so surface immediately (no retry,
                    # no failover — every address of this partition
                    # serves the same map or newer)
                    (ln,) = _U32.unpack(_recv_exact(conn, _U32.size))
                    map_json = _recv_exact(conn, ln)
                    self._release(conn)
                    self._breaker.record_success()
                    if rpc_span is not None:
                        rpc_span.log_kv(event="sidecar.stale_map")
                    raise StaleMapError(
                        f"sidecar at {self._path} rejected the frame's "
                        f"partition-map routing",
                        map_json,
                    )
                if status == bytes([STATUS_STALE_EPOCH]):
                    # the owner refused the write: it serves an OLDER
                    # epoch than this client has seen — a resurrected
                    # stale primary. The write was NOT applied; fail over
                    # (safe to re-send) instead of trusting stale state.
                    (stale_epoch,) = _U32.unpack(
                        _recv_exact(conn, _U32.size)
                    )
                    self._release(conn)
                    self._breaker.record_success()
                else:
                    if status == bytes([STATUS_OK_EPOCH]):
                        (srv_epoch,) = _U32.unpack(
                            _recv_exact(conn, _U32.size)
                        )
                        if srv_epoch > self._epoch_known:
                            self._epoch_known = srv_epoch
                    (n,) = _U32.unpack(_recv_exact(conn, _U32.size))
                    out = np.frombuffer(
                        _recv_exact(conn, 4 * n), dtype=np.uint32
                    )
            except CacheError:
                raise
            except (OSError, ConnectionError) as e:
                conn.close()
                if pooled and not redialed:
                    # idle-stale pooled socket (sidecar restart signature):
                    # the whole pool is stale — evict it and redial once for
                    # free, outside the retry budget, so a restart costs
                    # zero failed requests
                    redialed = True
                    self._evict_pool()
                    if self._c_redial is not None:
                        self._c_redial.inc()
                    if rpc_span is not None:
                        rpc_span.log_kv(
                            event="sidecar.redial", error=str(e)
                        )
                    continue
                attempt += 1
                if attempt > self._retries:
                    self._breaker.record_failure()
                    if fail_over_or_raise(f"transport failure: {e}"):
                        continue
                    raise CacheError(f"sidecar transport failure: {e}") from e
                if self._c_retry is not None:
                    self._c_retry.inc()
                if rpc_span is not None:
                    rpc_span.log_kv(
                        event="sidecar.retry",
                        attempt=attempt,
                        cause="transport",
                        error=str(e),
                    )
                self._sleep(self._backoff(attempt))
                continue
            if stale_epoch is not None:
                if rpc_span is not None:
                    rpc_span.log_kv(
                        event="sidecar.stale_epoch",
                        server_epoch=stale_epoch,
                        known_epoch=self._epoch_known,
                    )
                if fail_over_or_raise(
                    f"stale primary (epoch {stale_epoch} < "
                    f"{self._epoch_known})"
                ):
                    continue
                raise CacheError(
                    f"sidecar at {self._path} is a stale primary "
                    f"(epoch {stale_epoch}, cluster at "
                    f"{self._epoch_known}) and no other address answers"
                )
            self._release(conn)
            self._breaker.record_success()
            if self._h_rpc is not None:
                self._h_rpc.record((time.perf_counter() - t0) * 1e3)
            return out

    def flush(self) -> None:
        pass  # submits are synchronous end to end

    def close(self) -> None:
        if self._shm is not None:
            self._shm.close()
        with self._pool_lock:
            self._closed = True
            for conn in self._pool:
                conn.close()
            self._pool.clear()


def cluster_rpc(
    address: str, op: int, payload: bytes = b"", timeout: float = 30.0
) -> bytes:
    """One cluster admin RPC (OP_MAP_GET/SET, OP_RESHARD_PULL/PUSH)
    against a device owner: dial, send, read u8 status | u32 len | blob,
    return the blob. Deliberately pool-less and retry-less — the reshard
    coordinator and admin tools run off the hot path and want failures
    loud, not absorbed. unix and tcp:// addresses only (admin ops ride
    the same trust boundary as the socket itself)."""
    scheme, target = parse_sidecar_address(address)
    if scheme == "tls":
        raise CacheError("cluster admin RPCs do not ride tls:// addresses")
    if scheme == "unix":
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.settimeout(timeout)
        try:
            conn.connect(target)
        except OSError as e:
            conn.close()
            raise CacheError(f"cannot reach owner at {address}: {e}") from e
    else:
        try:
            conn = socket.create_connection(target, timeout=timeout)
        except OSError as e:
            raise CacheError(f"cannot reach owner at {address}: {e}") from e
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    try:
        conn.sendall(_HDR.pack(MAGIC, VERSION, op, 0) + payload)
        status = _recv_exact(conn, 1)
        (ln,) = _U32.unpack(_recv_exact(conn, _U32.size))
        body = _recv_exact(conn, ln)
        if status != b"\x00":
            raise CacheError(
                f"cluster op {op} failed on {address}: {body.decode(errors='replace')}"
            )
        return body
    except (OSError, ConnectionError) as e:
        raise CacheError(f"cluster op {op} transport failure on {address}: {e}") from e
    finally:
        conn.close()


def admin_set_faults(
    address: str,
    spec: str,
    seed: int | None = None,
    timeout: float = 30.0,
) -> dict:
    """Replace a live owner's fault rule set (OP_FAULTS_SET); returns the
    resulting FaultInjector.describe() document. A junk spec raises
    CacheError with the parse error — nothing changed server-side."""
    import json as _json

    doc: dict = {"spec": spec}
    if seed is not None:
        doc["seed"] = int(seed)
    payload = _json.dumps(doc).encode()
    body = cluster_rpc(
        address,
        OP_FAULTS_SET,
        _U32.pack(len(payload)) + payload,
        timeout=timeout,
    )
    return _json.loads(body.decode())


def admin_set_clock(
    address: str,
    offset_s: float = 0.0,
    drift_ppm: float = 0.0,
    timeout: float = 30.0,
) -> dict:
    """Step/drift a live owner's clock authority (OP_CLOCK_SET); defaults
    reset the skew. Returns {"unix_now", "skew"} as the owner now sees
    them — the chaos clock-skew nemesis over the wire."""
    import json as _json

    payload = _json.dumps(
        {"offset_s": float(offset_s), "drift_ppm": float(drift_ppm)}
    ).encode()
    body = cluster_rpc(
        address,
        OP_CLOCK_SET,
        _U32.pack(len(payload)) + payload,
        timeout=timeout,
    )
    return _json.loads(body.decode())


def new_sidecar_cache_from_settings(
    settings, base_limiter, stats_scope=None, fault_injector=None,
    lease_table=None,
):
    """BACKEND_TYPE=tpu-sidecar factory: a TpuRateLimitCache whose device
    driver is the remote sidecar (runner.py backend switch). With
    SIDECAR_ADDRS set the client gets the whole failover list (primary
    first); unset, it is exactly the single-address legacy client."""
    from .tpu import TpuRateLimitCache

    return TpuRateLimitCache(
        base_limiter,
        lease_table=lease_table,
        engine=SidecarEngineClient(
            settings.sidecar_addresses(),
            tls_ca=settings.sidecar_tls_ca,
            tls_cert=settings.sidecar_tls_cert,
            tls_key=settings.sidecar_tls_key,
            tls_server_name=settings.sidecar_tls_server_name,
            scope=stats_scope,
            connect_timeout=settings.sidecar_connect_timeout,
            rpc_deadline=settings.sidecar_rpc_deadline,
            retries=settings.sidecar_retries,
            retry_backoff=settings.sidecar_retry_backoff,
            retry_backoff_max=settings.sidecar_retry_backoff_max,
            breaker_threshold=settings.sidecar_breaker_threshold,
            breaker_reset=settings.sidecar_breaker_reset,
            fault_injector=fault_injector,
            shm_control_path=settings.shm_control_path(),
            shm_ring_rows=settings.shm_ring_rows_count(),
        ),
    )
