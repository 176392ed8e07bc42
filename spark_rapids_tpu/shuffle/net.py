"""Multi-host shuffle data plane: TCP block server + heartbeat discovery +
flow-controlled fetch iterator.

Reference architecture reproduced (over DCN sockets instead of UCX/RDMA):

  * ShuffleBlockServer    — serves kudo-wire blocks by (shuffle_id,
                            reduce partition) to peers
                            (RapidsShuffleServer / BufferSendState)
  * HeartbeatRegistry     — executors register and poll for new peers; the
                            driver-side RapidsShuffleHeartbeatManager.scala
                            (registerExecutor/executorHeartbeat) shape,
                            served over the same wire protocol
  * BlockFetchIterator    — pulls blocks from every peer with a bounded
                            in-flight byte budget (the throttle/bounce-
                            buffer role of RapidsShuffleIterator +
                            BufferReceiveState)
  * TcpShuffleTransport   — the ShuffleTransport SPI impl gluing these
                            under the exchange exec (mode=MULTIPROCESS)

Wire protocol: control messages are 4-byte big-endian header length +
JSON header + optional raw payload (length in the header); the hot fetch
path uses BINARY fixed-width framing (``fetch_many``: one round-trip
streams many blocks) so the JSON encode/decode cost is paid only on
control messages (register, heartbeat, list_blocks, shuffle membership).
Connections are PERSISTENT: one pooled socket per peer, reused across
requests and shuffles, with reconnect-on-error — the reference keeps UCX
endpoints warm the same way; cold connects per request were the dominant
reduce-side cost of the v1 plane.
"""
from __future__ import annotations

import json
import os
import socket
import socketserver
import struct
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.shuffle.stats import SHUFFLE_COUNTERS
from spark_rapids_tpu.testing.chaos import CHAOS
from spark_rapids_tpu.utils.checksum import frame_checksum, verify_frame
from spark_rapids_tpu.utils.retry_budget import (
    RetryBudget, RetryBudgetExhausted)


class BlockCorruptionError(OSError):
    """A fetched shuffle frame failed its checksum.  OSError family so
    transport-level retry/peer-loss handling covers it without new
    plumbing; the fetch path re-fetches from the serving peer before
    letting it escalate."""


class PeerLostError(OSError):
    """A shuffle participant that owes map output is unreachable.
    OSError family: the cluster layer treats it as retryable (the driver
    resubmits scoped to survivors)."""


#: verify checksums on received frames (spark.rapids.shuffle.checksum
#: .enabled).  Frames always CARRY a checksum slot on the wire — a crc
#: of 0 means "not checksummed" — so toggling this never desyncs framing.
_CHECKSUM = [True]


def set_checksum_enabled(enabled: bool) -> None:
    _CHECKSUM[0] = bool(enabled)


def checksum_enabled() -> bool:
    return _CHECKSUM[0]


#: network retry-budget shape (spark.rapids.network.retry.*): retries of
#: one RPC/fetch against one peer, bounded exponential backoff.
_NET_BUDGET = {"max_attempts": 4, "base_delay_s": 0.05, "max_delay_s": 2.0}


def set_network_retry(max_attempts: int, base_delay_s: float,
                      max_delay_s: float) -> None:
    _NET_BUDGET.update(max_attempts=int(max_attempts),
                       base_delay_s=float(base_delay_s),
                       max_delay_s=float(max_delay_s))


def network_budget(name: str) -> RetryBudget:
    return RetryBudget(name, **_NET_BUDGET)


# -- framing ------------------------------------------------------------------

def _send_msg(sock: socket.socket, header: dict,
              payload: bytes = b"") -> None:
    h = dict(header)
    h["payload_len"] = len(payload)
    raw = json.dumps(h).encode("utf-8")
    sock.sendall(struct.pack(">I", len(raw)) + raw + payload)


def _recv_exact(sock: socket.socket, n: int, what: str = "",
                peer=None) -> bytes:
    out = bytearray()
    while len(out) < n:
        chunk = sock.recv(n - len(out))
        if not chunk:
            # name the peer, the progress, and the in-flight request so
            # a truncated stream is diagnosable from the error alone
            raise ConnectionError(
                f"short read{' from ' + repr(peer) if peer else ''}: "
                f"peer closed after {len(out)}/{n} bytes"
                + (f" during {what}" if what else ""))
        out.extend(chunk)
    return bytes(out)


def _recv_msg(sock: socket.socket, peer=None) -> Tuple[dict, bytes]:
    (hlen,) = struct.unpack(
        ">I", _recv_exact(sock, 4, "control header length", peer))
    header = json.loads(
        _recv_exact(sock, hlen, "control header", peer).decode("utf-8"))
    payload = _recv_exact(sock, header.get("payload_len", 0),
                          f"control payload (op={header.get('op')!r})",
                          peer)
    return header, payload


# Binary fetch framing.  The leading word distinguishes a binary request
# from a JSON header length: real JSON headers are small, so a word with
# the top bit set can never be a header length.
#   request:  >I BIN_FETCH | >Q shuffle_id | >I partition | >I nblocks
#             | nblocks * >I block index
#   response: >I nblocks | per block (>Q length, >I crc32, raw bytes)
#             (crc 0 = frame not checksummed; see utils/checksum.py)
BIN_FETCH = 0xFFFF_FE7C
_BIN_REQ_FIXED = struct.Struct(">QII")
_BIN_BLOCK_HDR = struct.Struct(">QI")


def _send_fetch_many(sock: socket.socket, shuffle_id: int, partition: int,
                     blocks: List[int]) -> None:
    sock.sendall(struct.pack(">I", BIN_FETCH)
                 + _BIN_REQ_FIXED.pack(shuffle_id, partition, len(blocks))
                 + struct.pack(f">{len(blocks)}I", *blocks))


def _recv_fetch_many(sock: socket.socket,
                     peer=None, ctx: str = "") -> List[Tuple[bytes, int]]:
    """Receive the binary fetch response: [(payload, stored crc)]."""
    CHAOS.raise_if("shuffle.fetch.disconnect", ConnectionResetError)
    what = f"fetch response{' for ' + ctx if ctx else ''}"
    (n,) = struct.unpack(">I", _recv_exact(sock, 4, what, peer))
    out = []
    for i in range(n):
        ln, crc = _BIN_BLOCK_HDR.unpack(
            _recv_exact(sock, _BIN_BLOCK_HDR.size,
                        f"{what} block {i}/{n} header", peer))
        out.append((_recv_exact(sock, ln, f"{what} block {i}/{n} "
                                f"({ln} bytes)", peer), crc))
    return out


# -- persistent per-peer connections ------------------------------------------

class PooledConnection:
    """One long-lived socket to a peer, reused across requests and
    shuffles.  On any transport error the socket is dropped and the
    request retried once on a fresh connect (the server may have
    restarted, or an idle connection may have been reaped).

    Requests are serialized by socket OWNERSHIP HANDOFF, not by holding
    a lock across the IO: a round-trip checks the socket out under the
    condition, runs connect/send/recv with NO lock held, and checks it
    back in.  Holding the lock through the IO (the previous design) let
    one peer's 60s socket timeout block close()/connection_count() and
    any other thread touching this connection's state — the
    blocking-under-lock defect tpu-lint's lock checker flags."""

    def __init__(self, addr: Tuple[str, int], timeout: float = 60.0):
        self.addr = tuple(addr)
        self.timeout = timeout
        self._cv = threading.Condition()
        self._sock: Optional[socket.socket] = None
        self._busy = False
        self._closed = False

    def _connect(self) -> socket.socket:
        CHAOS.raise_if("shuffle.connect", ConnectionRefusedError)
        sock = socket.create_connection(self.addr, timeout=self.timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        SHUFFLE_COUNTERS.add(connections_opened=1)
        return sock

    @staticmethod
    def _close_sock(sock: Optional[socket.socket]) -> None:
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def _checkout(self) -> Optional[socket.socket]:
        """Take exclusive ownership of the pooled socket (may be None =
        caller connects).  A new request also un-latches close(): reuse
        after close means the caller wants the connection back.  The
        ownership wait is a blessed cancellable_wait: a cancelled query
        queued behind another thread's in-flight round-trip wakes with
        QueryCancelled instead of inheriting the peer's 60s timeout."""
        from spark_rapids_tpu.utils.cancel import cancellable_wait
        with self._cv:
            cancellable_wait(self._cv, predicate=lambda: not self._busy,
                             site="shuffle.conn.checkout")
            self._busy = True
            self._closed = False
            sock, self._sock = self._sock, None
        return sock

    def _checkin(self, sock: Optional[socket.socket]) -> None:
        """Return ownership; pool the healthy socket unless close() was
        called while the request was in flight."""
        with self._cv:
            self._busy = False
            if sock is not None and not self._closed:
                self._sock, sock = sock, None
            self._cv.notify()
        self._close_sock(sock)   # socket close runs outside the lock too

    def _roundtrip(self, send, recv, retriable: bool = True):
        """``retriable=False`` for NON-IDEMPOTENT ops (e.g. the driver's
        destructive get_task pop): a retry after a response-phase failure
        would re-execute a request the server may already have processed,
        silently losing its effect.  The socket is dropped either way, so
        the CALLER's next (distinct) request reconnects cleanly — callers
        of non-retriable ops decide themselves whether a single failure
        is tolerable (executor_main tolerates one stale-socket poll).

        Retriable ops retry on a fresh connect under a bounded-backoff
        ``RetryBudget`` (spark.rapids.network.retry.*); exhaustion raises
        ``RetryBudgetExhausted`` naming the budget, chained from the last
        transport error — never an unbounded reconnect loop."""
        sock = self._checkout()
        clean = False
        try:
            budget = (network_budget(f"shuffle.rpc:{self.addr[0]}:"
                                     f"{self.addr[1]}")
                      if retriable else None)
            while True:
                try:
                    if sock is None:
                        sock = self._connect()
                    send(sock)
                    out = recv(sock)
                    clean = True
                    return out
                except (ConnectionError, OSError, struct.error,
                        socket.timeout) as e:
                    self._close_sock(sock)
                    sock = None
                    if budget is None:
                        raise
                    budget.backoff(error=e)   # raises RetryBudgetExhausted
                    SHUFFLE_COUNTERS.add(fetch_retries=1)
        finally:
            if not clean and sock is not None:
                # an exception OUTSIDE the transport-error tuple (e.g. a
                # malformed JSON header) left the socket mid-protocol
                # with unread bytes buffered; pooling it would desync
                # every later request on this peer
                self._close_sock(sock)
                sock = None
            self._checkin(sock)

    def request(self, header: dict, payload: bytes = b"",
                retriable: bool = True) -> Tuple[dict, bytes]:
        return self._roundtrip(
            lambda s: _send_msg(s, header, payload),
            lambda s: _recv_msg(s, peer=self.addr),
            retriable=retriable)

    def fetch_many(self, shuffle_id: int, partition: int,
                   blocks: List[int]) -> List[bytes]:
        """Binary hot path: many blocks per round-trip, no JSON.
        Idempotent, so safe to retry on a fresh connection.  Each frame
        is verified against its map-side checksum (when enabled); a
        mismatch raises ``BlockCorruptionError`` — the fetch iterator
        re-fetches from the serving peer before escalating."""
        ctx = f"shuffle {shuffle_id} partition {partition}"
        out = self._roundtrip(
            lambda s: _send_fetch_many(s, shuffle_id, partition, blocks),
            lambda s: _recv_fetch_many(s, peer=self.addr, ctx=ctx))
        if len(out) != len(blocks):
            # the server drops unknown indices rather than erroring; a
            # short response means the peer lost map output (e.g. a
            # restart the reconnect path papered over) — fail LOUDLY,
            # silently-partial reduce data is the one unacceptable outcome.
            # PeerLostError (OSError family) so the cluster layer treats
            # it as retryable and resubmits scoped to survivors
            raise PeerLostError(
                f"peer {self.addr} returned {len(out)}/{len(blocks)} "
                f"blocks for shuffle {shuffle_id} partition {partition} "
                "(map output lost?)")
        if checksum_enabled():
            bad = [i for i, (b, crc) in enumerate(out)
                   if not verify_frame(b, crc)]
            SHUFFLE_COUNTERS.add(
                checksums_verified=sum(1 for _, crc in out if crc))
            if bad:
                SHUFFLE_COUNTERS.add(checksum_failures=len(bad))
                raise BlockCorruptionError(
                    f"checksum mismatch on block(s) {bad} of {ctx} from "
                    f"peer {self.addr} (frame corrupted in transit or "
                    "at rest)")
        SHUFFLE_COUNTERS.add(fetch_requests=1, blocks_fetched=len(out),
                             bytes_fetched=sum(len(b) for b, _ in out))
        return [b for b, _ in out]

    def close(self) -> None:
        with self._cv:
            self._closed = True
            sock, self._sock = self._sock, None
        self._close_sock(sock)


class ConnectionPool:
    """addr -> PooledConnection, process-wide (connections survive
    individual transports AND shuffles; RapidsShuffleTransport keeps its
    UCX endpoint cache the same way)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._conns: Dict[Tuple[str, int], PooledConnection] = {}

    def get(self, addr: Tuple[str, int]) -> PooledConnection:
        addr = tuple(addr)
        with self._lock:
            conn = self._conns.get(addr)
            if conn is None:
                conn = self._conns[addr] = PooledConnection(addr)
            return conn

    def connection_count(self, addr: Tuple[str, int]) -> int:
        """Live pooled connections for addr (0 or 1 by construction)."""
        with self._lock:
            conn = self._conns.get(tuple(addr))
        return int(conn is not None and conn._sock is not None)

    def close_all(self) -> None:
        with self._lock:
            conns, self._conns = list(self._conns.values()), {}
        for c in conns:
            c.close()


_POOL = ConnectionPool()


def connection_pool() -> ConnectionPool:
    return _POOL


def _request(addr: Tuple[str, int], header: dict, payload: bytes = b"",
             retriable: bool = True) -> Tuple[dict, bytes]:
    """Control-message RPC over the pooled persistent connection (its
    fixed timeout applies; a per-call timeout would need its own
    socket and defeat the pooling)."""
    return _POOL.get(addr).request(header, payload, retriable=retriable)


# -- block store + server -----------------------------------------------------

class BlockStore:
    """Local map-output store: (shuffle_id, partition) -> list of
    (wire block, checksum).  Thread-safe; shared between the writer and
    the server.  Checksums are computed ONCE at put() (the map side) and
    travel with every serve, so re-fetches never recompute them.

    Durability extensions (docs/fault_tolerance.md durable shuffle):

      * every shuffle's primary blocks carry the task ATTEMPT that wrote
        them, so a lost first-commit race can drop exactly its own
        attempt's blocks (``drop_attempt``) without touching replicas or
        other attempts' data;
      * a REPLICA side-table holds other executors' replicated blocks
        keyed by (shuffle, partition, source logical id).  Replicas are
        served only by explicit replica reads — never by the primary
        fetch path, which would double every reduce row;
      * an optional PERSIST DIR (spill-backed fallback when the
        replication factor is 1): every primary put also lands on local
        disk with its CRC in the filename, and a restarted executor with
        the same directory re-serves blocks it no longer has in memory.
    """

    def __init__(self, persist_dir: Optional[str] = None):
        self._lock = threading.Lock()
        #: (sid, partition) -> [(block, crc, attempt)].  One node may
        #: legitimately hold blocks of SEVERAL attempts for one shuffle
        #: (its own rank's output plus an adopted rank's re-dispatch), so
        #: the attempt tag is per BLOCK: a lost commit race or failed
        #: task drops exactly its own attempt's blocks and nothing else.
        self._blocks: Dict[Tuple[int, int],
                           List[Tuple[bytes, int, int]]] = {}
        self._complete: set = set()
        #: sid -> {logical slot id -> committed attempt}.  One node may
        #: COMMIT several logical slots of one shuffle (its own rank plus
        #: adopted speculative/re-dispatch wins); serving is filtered to
        #: committed attempts per slot, so an uncommitted (or beaten)
        #: attempt's blocks can never reach a reader.
        self._commits: Dict[int, Dict[str, int]] = {}
        #: (sid, partition, src) -> (blocks [(bytes, crc, attempt)],
        #: commit-map snapshot {slot: attempt} at push time).  The
        #: snapshot makes staleness DETECTABLE: a replica pushed before
        #: some slot committed simply has no entry for it, and the
        #: reader escalates instead of silently serving fewer rows.
        self._replicas: Dict[Tuple[int, int, str],
                             Tuple[List[Tuple[bytes, int, int]],
                                   Dict[str, int]]] = {}
        self._persist_dir: Optional[str] = None
        #: (sid, partition) persist-dir lookups that found nothing — the
        #: common case for partitions this node never wrote; caching the
        #: miss avoids an os.listdir per read
        self._persist_miss: set = set()
        if persist_dir:
            self.set_persist_dir(persist_dir)

    # -- persistence (spill-backed durability fallback) -----------------------

    def set_persist_dir(self, persist_dir: str) -> None:
        """Enable spill-backed persistence: primary puts also write
        ``<dir>/<sid>_<partition>_<idx>_<attempt>_<crc08x>.blk`` and
        reads fall back to disk when memory misses (an executor
        restarted with the same directory re-serves its committed map
        output).  The attempt tag in the name lets ``drop_shuffle_attempt``
        remove exactly the loser's files — a dropped attempt must never
        resurrect from disk next to the winner's remote copy."""
        persist_dir = str(persist_dir or "")
        if persist_dir:
            os.makedirs(persist_dir, exist_ok=True)  # before publishing:
            # a put() racing this call must never write into a missing dir
        with self._lock:
            self._persist_dir = persist_dir or None
            self._persist_miss.clear()

    def _persist_block(self, shuffle_id: int, partition: int, idx: int,
                       block: bytes, crc: int, attempt: int) -> None:
        path = os.path.join(
            self._persist_dir,
            f"{shuffle_id}_{partition}_{idx}_{attempt}_{crc:08x}.blk")
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(block)
        os.replace(tmp, path)       # readers never see a torn block
        SHUFFLE_COUNTERS.add(blocks_persisted=1)

    def _load_persisted(self, shuffle_id: int,
                        partition: int) -> List[Tuple[bytes, int]]:
        """Reload a partition's persisted blocks (index order).  Caller
        holds no lock; results are cached back into memory."""
        prefix = f"{shuffle_id}_{partition}_"
        found = []
        try:
            names = os.listdir(self._persist_dir)
        except OSError:
            return []
        for name in names:
            if not (name.startswith(prefix) and name.endswith(".blk")):
                continue
            parts = name[:-4].split("_")
            if len(parts) != 5 or parts[1] != str(partition):
                continue
            try:
                idx, attempt, crc = (int(parts[2]), int(parts[3]),
                                     int(parts[4], 16))
            except ValueError:
                continue
            try:
                with open(os.path.join(self._persist_dir, name),
                          "rb") as f:
                    found.append((idx, (f.read(), crc, attempt)))
            except OSError:
                continue
        found.sort(key=lambda t: t[0])
        blocks = [t for _, t in found]
        if blocks:
            SHUFFLE_COUNTERS.add(blocks_recovered_disk=len(blocks))
            with self._lock:
                self._blocks.setdefault((shuffle_id, partition), blocks)
        return [(b, crc) for b, crc, _ in blocks]

    def _drop_persisted(self, shuffle_id: int,
                        attempt: Optional[int] = None) -> None:
        """Remove persisted files for a shuffle — all of them, or (with
        ``attempt``) only the files that attempt wrote."""
        prefix = f"{shuffle_id}_"
        try:
            names = os.listdir(self._persist_dir)
        except OSError:
            return
        for name in names:
            if not (name.startswith(prefix) and (
                    name.endswith(".blk") or name.endswith(".complete")
                    or name.endswith(".commits"))):
                continue
            if attempt is not None:
                # attempt-scoped drop removes only that attempt's .blk
                # files; the .complete/.commits markers stay valid for
                # the surviving slots (drop_commit rewrites .commits)
                if not name.endswith(".blk"):
                    continue
                parts = name[:-4].split("_")
                if len(parts) != 5 or parts[3] != str(attempt):
                    continue
            try:
                os.remove(os.path.join(self._persist_dir, name))
            except OSError:
                pass

    # -- primary blocks -------------------------------------------------------

    def put(self, shuffle_id: int, partition: int, block: bytes,
            attempt: int = 0) -> None:
        crc = frame_checksum(block) if checksum_enabled() else 0
        if crc:
            SHUFFLE_COUNTERS.add(checksums_computed=1)
        persist = None
        with self._lock:
            lst = self._blocks.setdefault((shuffle_id, partition), [])
            lst.append((block, crc, int(attempt)))
            self._persist_miss.discard((shuffle_id, partition))
            if self._persist_dir:
                persist = (len(lst) - 1, self._persist_dir)
        if persist is not None:
            self._persist_block(shuffle_id, partition, persist[0],
                                block, crc, int(attempt))

    def mark_complete(self, shuffle_id: int) -> None:
        """Map output for this shuffle is fully written on this node."""
        with self._lock:
            self._complete.add(shuffle_id)
            persist_dir = self._persist_dir
        if persist_dir:
            try:
                with open(os.path.join(persist_dir,
                                       f"{shuffle_id}_.complete"),
                          "w") as f:
                    f.write("1")
            except OSError:
                pass    # persistence is best-effort; memory copy serves

    def is_complete(self, shuffle_id: int) -> bool:
        with self._lock:
            if shuffle_id in self._complete:
                return True
            persist_dir = self._persist_dir
        if persist_dir and os.path.exists(
                os.path.join(persist_dir, f"{shuffle_id}_.complete")):
            with self._lock:
                self._complete.add(shuffle_id)
            return True
        return False

    def note_commit(self, shuffle_id: int, slot: str,
                    attempt: int) -> None:
        """Record that ``slot``'s map output on this node is the blocks
        tagged ``attempt`` (called when a map commit WINS its logical
        slot).  Slot-filtered serving reads only committed attempts."""
        with self._lock:
            self._commits.setdefault(int(shuffle_id), {})[str(slot)] = \
                int(attempt)
        self._persist_commits(int(shuffle_id))

    def drop_commit(self, shuffle_id: int, slot: str) -> None:
        with self._lock:
            self._commits.get(int(shuffle_id), {}).pop(str(slot), None)
        self._persist_commits(int(shuffle_id))

    def _persist_commits(self, shuffle_id: int) -> None:
        """Mirror the commit map next to the persisted blocks — a
        restarted executor must keep serving SLOT-FILTERED reads, not
        just raw blocks.  Best effort, like the .complete marker."""
        with self._lock:
            persist_dir = self._persist_dir
            snap = dict(self._commits.get(shuffle_id, {}))
        if not persist_dir:
            return
        try:
            path = os.path.join(persist_dir, f"{shuffle_id}_.commits")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(snap, f)
            os.replace(tmp, path)
        except OSError:
            pass

    def commits(self, shuffle_id: int) -> Dict[str, int]:
        """{logical slot -> committed attempt} for this node's store."""
        with self._lock:
            got = self._commits.get(int(shuffle_id))
            persist_dir = self._persist_dir
        if got is None and persist_dir:
            try:
                with open(os.path.join(persist_dir,
                                       f"{shuffle_id}_.commits")) as f:
                    got = {str(k): int(v) for k, v in json.load(f).items()}
            except (OSError, ValueError):
                got = None
            if got is not None:
                with self._lock:
                    got = self._commits.setdefault(int(shuffle_id), got)
        return dict(got or {})

    def get(self, shuffle_id: int, partition: int) -> List[bytes]:
        return [b for b, _ in self.get_with_crcs(shuffle_id, partition)]

    def _entries(self, shuffle_id: int,
                 partition: int) -> List[Tuple[bytes, int, int]]:
        with self._lock:
            got = self._blocks.get((shuffle_id, partition))
            persist_dir = self._persist_dir
            missed = (shuffle_id, partition) in self._persist_miss
        if got is None and persist_dir and not missed:
            self._load_persisted(shuffle_id, partition)
            with self._lock:
                got = self._blocks.get((shuffle_id, partition))
                if got is None:
                    self._persist_miss.add((shuffle_id, partition))
        return list(got or [])

    def get_with_crcs(self, shuffle_id: int,
                      partition: int) -> List[Tuple[bytes, int]]:
        return [(b, crc) for b, crc, _ in self._entries(shuffle_id,
                                                        partition)]

    def get_entries(self, shuffle_id: int, partition: int
                    ) -> List[Tuple[bytes, int, int]]:
        """[(block, crc, attempt)] — the replication push needs the
        attempt tags to frame a slot-filtered snapshot."""
        return self._entries(shuffle_id, partition)

    def get_committed(self, shuffle_id: int,
                      partition: int) -> List[bytes]:
        """Local read of every COMMITTED slot's blocks (the reduce
        side's own-store short-circuit).  Falls back to the unfiltered
        list when no commit map exists (standalone shuffles)."""
        entries = self._entries(shuffle_id, partition)
        committed = set(self.commits(shuffle_id).values())
        if not committed:
            return [b for b, _, _ in entries]
        return [b for b, _, a in entries if a in committed]

    def sizes(self, shuffle_id: int, partition: int) -> List[int]:
        return [len(b) for b, _ in self.get_with_crcs(shuffle_id,
                                                      partition)]

    def sizes_ex(self, shuffle_id: int, partition: int
                 ) -> Tuple[List[int], List[int], Dict[str, int]]:
        """(sizes, per-block attempt tags, {slot -> committed attempt})
        — everything a reader needs to select exactly ONE slot's blocks
        by index from this node's union list."""
        entries = self._entries(shuffle_id, partition)
        return ([len(b) for b, _, _ in entries],
                [a for _, _, a in entries],
                self.commits(shuffle_id))

    def partitions(self, shuffle_id: int) -> List[int]:
        """Partitions with resident primary blocks for this shuffle
        (the replication push enumerates these)."""
        with self._lock:
            return sorted(p for sid, p in self._blocks
                          if sid == shuffle_id)

    # -- replica side-table ---------------------------------------------------

    def put_replica(self, shuffle_id: int, partition: int, src: str,
                    blocks: List[Tuple[bytes, int]],
                    attempts: Optional[List[int]] = None,
                    commits: Optional[Dict[str, int]] = None) -> None:
        """Store a peer's replicated partition block list (REPLACES any
        previous copy: replication pushes whole partitions, so a retried
        push stays idempotent).  Block order matches the source's primary
        list — replica fetches address the same indices.  ``attempts``
        tags each block and ``commits`` snapshots the source's
        slot->attempt commit map at push time, so a reader can both
        select one slot's blocks and DETECT a snapshot that predates a
        slot's commit (no entry -> escalate, never under-serve)."""
        attempts = list(attempts) if attempts is not None \
            else [0] * len(blocks)
        tagged = [(b, crc, a) for (b, crc), a in zip(blocks, attempts)]
        with self._lock:
            self._replicas[(shuffle_id, partition, str(src))] = (
                tagged, dict(commits or {}))

    def get_replica_with_crcs(self, shuffle_id: int, partition: int,
                              src: str) -> List[Tuple[bytes, int]]:
        with self._lock:
            tagged, _ = self._replicas.get(
                (shuffle_id, partition, str(src)), ([], {}))
            return [(b, crc) for b, crc, _ in tagged]

    def replica_sizes(self, shuffle_id: int, partition: int,
                      src: str) -> List[int]:
        with self._lock:
            tagged, _ = self._replicas.get(
                (shuffle_id, partition, str(src)), ([], {}))
            return [len(b) for b, _, _ in tagged]

    def replica_sizes_ex(self, shuffle_id: int, partition: int, src: str
                         ) -> Tuple[List[int], List[int], Dict[str, int]]:
        with self._lock:
            tagged, commits = self._replicas.get(
                (shuffle_id, partition, str(src)), ([], {}))
            return ([len(b) for b, _, _ in tagged],
                    [a for _, _, a in tagged], dict(commits))

    def replica_keys(self) -> List[Tuple[int, int, str]]:
        with self._lock:
            return sorted(self._replicas)

    # -- teardown -------------------------------------------------------------

    def drop_shuffle(self, shuffle_id: int,
                     include_replicas: bool = True) -> None:
        with self._lock:
            for k in [k for k in self._blocks if k[0] == shuffle_id]:
                del self._blocks[k]
            self._complete.discard(shuffle_id)
            self._commits.pop(shuffle_id, None)
            for k in [k for k in self._persist_miss
                      if k[0] == shuffle_id]:
                self._persist_miss.discard(k)
            if include_replicas:
                for k in [k for k in self._replicas if k[0] == shuffle_id]:
                    del self._replicas[k]
            persist_dir = self._persist_dir
        if persist_dir:
            self._drop_persisted(shuffle_id)

    def drop_shuffle_attempt(self, shuffle_id: int, attempt: int) -> int:
        """Drop only ``attempt``'s blocks for one shuffle (the loser of
        a first-commit race): blocks other attempts wrote on this node —
        e.g. this executor's OWN rank output when it also adopted a lost
        rank under the same shuffle id — and replicas held for peers all
        survive.  Returns blocks dropped."""
        dropped = 0
        commits_changed = False
        with self._lock:
            for k in [k for k in self._blocks if k[0] == shuffle_id]:
                kept = [t for t in self._blocks[k] if t[2] != int(attempt)]
                dropped += len(self._blocks[k]) - len(kept)
                if kept:
                    self._blocks[k] = kept
                else:
                    del self._blocks[k]
            # commit records pointing at the dropped attempt go WITH the
            # blocks: a record left behind would make readers see "slot
            # committed here, zero matching blocks" — indistinguishable
            # from a legitimately empty partition, so they'd be silently
            # under-served instead of failing over to a replica
            cm = self._commits.get(shuffle_id, {})
            for slot in [s for s, a in cm.items() if a == int(attempt)]:
                del cm[slot]
                commits_changed = True
            persist_dir = self._persist_dir
        if persist_dir:
            # the loser's persisted files must go too, or a later memory
            # miss would resurrect them from disk beside the winner's
            # remote copy (doubled rows)
            self._drop_persisted(shuffle_id, attempt=int(attempt))
        if commits_changed:
            self._persist_commits(shuffle_id)
        return dropped

    def shuffle_ids(self) -> List[int]:
        with self._lock:
            return sorted({k[0] for k in self._blocks} | self._complete)

    def drop_attempt(self, query_id: int, attempt: int) -> int:
        """Drop only the PRIMARY blocks this node wrote for ``query_id``
        under ``attempt`` (the failed-task / lost-commit cleanup).
        Replicas held for other executors, and blocks other attempts
        committed on this node, are kept — they may be the only
        surviving copy of a committed map output."""
        dropped = 0
        if int(query_id) < 1:
            return 0
        for sid in self.shuffle_ids():
            if sid >> 16 == int(query_id):
                dropped += bool(self.drop_shuffle_attempt(sid,
                                                          int(attempt)))
        return dropped

    def drop_query(self, query_id: int) -> int:
        """Drop every shuffle belonging to a cluster query (deterministic
        id scheme: sid = query_id << 16 | exchange ordinal — see
        transport.set_cluster_query), including any replicas held for
        peers.  Returns the number of shuffles dropped; the driver
        broadcasts this on query teardown so a failed attempt can't leak
        its blocks (or satisfy a retry read)."""
        dropped = 0
        if int(query_id) < 1:
            # qid slot 0 is where standalone next_shuffle_id() sids live
            # (sid < 2**16); dropping "query 0" would collect them
            return 0
        replica_sids = {k[0] for k in self.replica_keys()}
        for sid in set(self.shuffle_ids()) | replica_sids:
            if sid >> 16 == int(query_id):
                self.drop_shuffle(sid)
                dropped += 1
        return dropped


class HeartbeatRegistry:
    """Executor discovery: id -> (host, port, last-seen).  The driver-side
    registry; executors poll `peers` to learn about new members
    (RapidsShuffleHeartbeatManager.executorHeartbeat)."""

    def __init__(self, timeout_s: float = 60.0,
                 exclude_threshold: int = 3):
        self._lock = threading.Lock()
        #: eid -> (host, port, last_seen, role)
        self._peers: Dict[str, Tuple[str, int, float, str]] = {}
        self.timeout_s = timeout_s
        #: ranks mid graceful drain (begin_drain..leave): still LIVE as
        #: fetch targets — their blocks serve until the drain completes
        #: — but never AVAILABLE capacity (_available_locked), so the
        #: autoscaler and rank_rings share one capacity definition and a
        #: draining rank can't be counted as a scale-in candidate twice
        #: or receive fresh primary dispatches
        self._draining: set = set()
        #: reported fetch failures after which a peer is excluded from
        #: the live view (spark.rapids.shuffle.peer.excludeAfterFailures);
        #: a fresh register() clears the record (a genuinely restarted
        #: executor may rejoin)
        self.exclude_threshold = int(exclude_threshold)
        self._failures: Dict[str, int] = {}
        self._next_shuffle = 0
        #: per-rank telemetry rings (utils/telemetry.py): executors
        #: piggyback their LATEST resource sample on the heartbeat (no
        #: new RPC); the driver keeps a bounded ring per rank and the
        #: `metrics` wire op serves them to tools/metrics_scrape.py.
        #: Legacy peers that send no sample simply have no ring.
        self._rank_rings: Dict[str, "deque"] = {}
        self.rank_ring_max = 240
        # per-shuffle participation: which LOGICAL participants WILL write
        # map output (declared at transport construction) and which have
        # finished.  Readers await completeness only from declared
        # participants, so a registered-but-idle worker can't stall every
        # read (MapOutputTracker role).
        self._participants: Dict[int, set] = {}
        self._map_complete: Dict[int, set] = {}
        #: first-commit-wins serving map: sid -> {logical participant ->
        #: physical executor that committed its map output}.  Speculative
        #: attempts and post-loss rank re-dispatches run AS a logical
        #: slot; the first physical commit wins, later ones are told so
        #: and drop their blocks by attempt.
        self._map_servers: Dict[int, Dict[str, str]] = {}
        #: replica catalog: (sid, source logical id) -> holder executor
        #: ids (the RapidsShuffleManager block-catalog role: where a map
        #: output's surviving copies live)
        self._replica_holders: Dict[Tuple[int, str], set] = {}

    def join_shuffle(self, shuffle_id: int, executor_id: str) -> None:
        with self._lock:
            self._participants.setdefault(shuffle_id, set()).add(executor_id)

    def map_complete(self, shuffle_id: int, executor_id: str,
                     physical_id: Optional[str] = None) -> bool:
        """Commit ``executor_id``'s (logical) map output for this
        shuffle, served by ``physical_id`` (defaults to the logical id).
        FIRST COMMIT WINS: returns True when this physical executor now
        serves the slot, False when another attempt already committed —
        the loser must drop its blocks (they'd double the reduce data if
        both copies ever served)."""
        physical = physical_id or executor_id
        with self._lock:
            self._participants.setdefault(shuffle_id, set()).add(executor_id)
            servers = self._map_servers.setdefault(shuffle_id, {})
            cur = servers.setdefault(executor_id, physical)
            won = cur == physical
            self._map_complete.setdefault(shuffle_id, set()).add(executor_id)
        return won

    def shuffle_status(self, shuffle_id: int
                       ) -> Tuple[List[str], List[str], Dict[str, str]]:
        with self._lock:
            return (sorted(self._participants.get(shuffle_id, ())),
                    sorted(self._map_complete.get(shuffle_id, ())),
                    dict(self._map_servers.get(shuffle_id, {})))

    # -- replica catalog ------------------------------------------------------

    def replica_announce(self, shuffle_id: int, src: str,
                         holder: str) -> None:
        with self._lock:
            self._replica_holders.setdefault(
                (int(shuffle_id), str(src)), set()).add(str(holder))
        SHUFFLE_COUNTERS.add(replica_announces=1)

    def replica_holders(self, shuffle_id: int, src: str) -> List[str]:
        with self._lock:
            return sorted(self._replica_holders.get(
                (int(shuffle_id), str(src)), ()))

    def catalog(self) -> dict:
        """The shuffle/replica catalog a joining executor syncs at
        registration: which shuffles exist, who committed what, and where
        the replicas live."""
        with self._lock:
            return {
                "shuffles": sorted(self._map_complete),
                "servers": {str(sid): dict(m)
                            for sid, m in self._map_servers.items()},
                "replicas": [[sid, src, sorted(holders)]
                             for (sid, src), holders
                             in sorted(self._replica_holders.items())],
            }

    def leave(self, executor_id: str) -> bool:
        """Graceful departure: remove the peer WITHOUT a failure record
        (unlike exclude) — it drained its blocks and may rejoin later.
        Its map commits and replica announcements survive, so readers
        resolve its slots through replicas."""
        with self._lock:
            present = executor_id in self._peers
            if present:
                del self._peers[executor_id]
            self._failures.pop(executor_id, None)
            self._rank_rings.pop(executor_id, None)
            self._draining.discard(executor_id)
        if present:
            SHUFFLE_COUNTERS.add(executors_left=1)
            from spark_rapids_tpu.utils.telemetry import record_event
            record_event("executor_leave", eid=executor_id)
        return present

    def next_shuffle_id(self) -> int:
        """Driver-coordinated shuffle ids: every host sees the same id for
        the same exchange (a per-process counter would interleave across
        hosts and mix shuffles)."""
        with self._lock:
            self._next_shuffle += 1
            return self._next_shuffle

    def declare_shuffle(self, shuffle_id: int, participants) -> None:
        """Coordinator-declared participant set (the MapOutputTracker
        role): readers wait for exactly these executors' map output.
        Without a declaration the set accrues dynamically from
        join_shuffle — correct once every participant has constructed its
        transport, but a reader racing a slow participant's *construction*
        can see a complete-looking subset; topologies where that race is
        possible must declare (the coordinator knows the worker set the
        query runs on, as Spark's scheduler does)."""
        with self._lock:
            self._participants.setdefault(shuffle_id, set()).update(
                participants)

    def register(self, executor_id: str, host: str, port: int,
                 role: str = "worker") -> None:
        with self._lock:
            joined = executor_id not in self._peers and role == "worker"
            self._peers[executor_id] = (host, port, time.time(), role)
            self._failures.pop(executor_id, None)
            # a (re)registration is a fresh membership: any stale drain
            # mark from a previous incarnation must not hide the rank
            # from capacity forever
            self._draining.discard(executor_id)
        if joined:
            SHUFFLE_COUNTERS.add(executors_joined=1)
            from spark_rapids_tpu.utils.telemetry import record_event
            record_event("executor_join", eid=executor_id)

    def report_failure(self, executor_id: str) -> bool:
        """An executor reported repeated fetch failures against this
        peer.  After ``exclude_threshold`` reports the peer is dropped
        from the live view so later reads stop fetching from it (the
        reference's BlockManager blacklisting role).  Returns True when
        this report excluded the peer."""
        with self._lock:
            n = self._failures.get(executor_id, 0) + 1
            self._failures[executor_id] = n
            excluded = (n >= self.exclude_threshold
                        and executor_id in self._peers)
            if excluded:
                del self._peers[executor_id]
                self._rank_rings.pop(executor_id, None)
        SHUFFLE_COUNTERS.add(peer_failures_reported=1,
                             peers_excluded=int(excluded))
        return excluded

    def exclude(self, executor_id: str) -> bool:
        """Drop a peer immediately (driver-observed executor loss: don't
        wait for its heartbeat record to age out before resubmitting).
        Returns True when the peer was present."""
        with self._lock:
            present = executor_id in self._peers
            if present:
                del self._peers[executor_id]
            self._failures[executor_id] = max(
                self._failures.get(executor_id, 0), self.exclude_threshold)
            self._rank_rings.pop(executor_id, None)
            # kill-during-scale-in: an excluded rank's drain mark dies
            # with it (it is no capacity of ANY kind now)
            self._draining.discard(executor_id)
        if present:
            SHUFFLE_COUNTERS.add(peers_excluded=1)
        return present

    def heartbeat(self, executor_id: str,
                  telemetry: Optional[dict] = None) -> None:
        """Refresh liveness; ``telemetry`` (the peer's latest resource
        sample, piggybacked on the beat) lands in the per-rank ring.
        Legacy peers pass None — liveness semantics are unchanged."""
        with self._lock:
            if executor_id in self._peers:
                h, p, _, role = self._peers[executor_id]
                self._peers[executor_id] = (h, p, time.time(), role)
                # telemetry only for REGISTERED peers: a stray beat from
                # an excluded/departed id must not resurrect its series
                if telemetry is not None and isinstance(telemetry, dict):
                    ring = self._rank_rings.get(executor_id)
                    if ring is None:
                        ring = deque(maxlen=self.rank_ring_max)
                        self._rank_rings[executor_id] = ring
                    # executors beat faster than they sample: dedupe by
                    # the sample timestamp so the ring holds distinct
                    # ticks
                    if not ring or ring[-1].get("t") != telemetry.get("t"):
                        ring.append(telemetry)

    # -- live capacity (ONE definition; the autoscaler's view) ----------------

    def _available_locked(self, now: float) -> set:
        """THE capacity predicate (caller holds the lock): a worker
        within the heartbeat window AND not mid-drain.  rank_rings,
        live_capacity and the driver's dispatch targeting all route
        through here — a draining or just-excluded rank can never be
        counted as available capacity by any of them."""
        return {eid for eid, (_h, _p, seen, role) in self._peers.items()
                if now - seen <= self.timeout_s and role == "worker"
                and eid not in self._draining}

    def begin_drain(self, executor_id: str) -> bool:
        """Mark a rank mid graceful drain: it stays a live fetch target
        (its blocks serve until it leaves) but stops counting as
        available capacity and must receive no fresh primary dispatch.
        Returns False for an unknown/stale peer."""
        now = time.time()
        with self._lock:
            rec = self._peers.get(executor_id)
            if rec is None or now - rec[2] > self.timeout_s:
                return False
            self._draining.add(executor_id)
        return True

    def end_drain(self, executor_id: str) -> None:
        """Un-mark a drain that was aborted (the rank stays a member)."""
        with self._lock:
            self._draining.discard(executor_id)

    def draining(self) -> List[str]:
        with self._lock:
            return sorted(self._draining)

    def live_capacity(self) -> Dict[str, List[str]]:
        """{"available": [...], "draining": [...]} over LIVE workers —
        the autoscaler's capacity view, same predicate as rank_rings."""
        now = time.time()
        with self._lock:
            avail = self._available_locked(now)
            draining = {eid for eid in self._draining
                        if eid in self._peers
                        and now - self._peers[eid][2] <= self.timeout_s}
            return {"available": sorted(avail),
                    "draining": sorted(draining)}

    def rank_rings(self) -> Dict[str, List[dict]]:
        """{executor_id: [samples...]} — the driver-held per-rank
        telemetry rings (the `metrics` wire op's cluster view).  Only
        AVAILABLE peers report (_available_locked: heartbeat-windowed,
        not draining): a dead or draining rank's last sample must not
        read as live capacity to the autoscaler, so those rings are
        omitted (and dropped on leave/exclude)."""
        now = time.time()
        with self._lock:
            live = self._available_locked(now)
            return {eid: list(ring)
                    for eid, ring in self._rank_rings.items()
                    if eid in live}

    def peers(self, workers_only: bool = False) -> Dict[str, Tuple[str, int]]:
        """Live peers; workers_only excludes registry-only driver nodes
        (they serve no map output and must not be fetched from).
        DRAINING ranks stay listed: readers still fetch their blocks
        until the drain completes — use live_capacity()/rank_rings()
        for the capacity view that excludes them."""
        now = time.time()
        with self._lock:
            return {eid: (h, p)
                    for eid, (h, p, seen, role) in self._peers.items()
                    if now - seen <= self.timeout_s
                    and (not workers_only or role == "worker")}


class ShuffleBlockServer:
    """Threaded TCP server exposing a BlockStore (+ optional registry when
    this process also plays the driver role)."""

    def __init__(self, store: BlockStore,
                 registry: Optional[HeartbeatRegistry] = None,
                 host: str = "127.0.0.1", port: int = 0):
        self.store = store
        self.registry = registry
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                # persistent connection: serve requests until the peer
                # hangs up (the pooled-client contract; one socket per
                # peer, reused across requests and shuffles)
                while True:
                    try:
                        if not self._serve_one():
                            return
                    except (ConnectionError, OSError, struct.error):
                        return

            def _serve_one(self) -> bool:
                try:
                    first = _recv_exact(self.request, 4, "request word",
                                        self.client_address)
                except ConnectionError:
                    return False
                (word,) = struct.unpack(">I", first)
                if word == BIN_FETCH:
                    sid, part, n = _BIN_REQ_FIXED.unpack(
                        _recv_exact(self.request, _BIN_REQ_FIXED.size,
                                    "fetch request", self.client_address))
                    idxs = struct.unpack(
                        f">{n}I",
                        _recv_exact(self.request, 4 * n, "fetch indices",
                                    self.client_address))
                    CHAOS.stall("shuffle.serve.stall")
                    blocks = outer.store.get_with_crcs(sid, part)
                    picked = [blocks[i] for i in idxs if i < len(blocks)]
                    parts = [struct.pack(">I", len(picked))]
                    for b, crc in picked:
                        # chaos corrupts the PAYLOAD only: the stored crc
                        # still describes the clean bytes, so the client's
                        # verify is what must catch the flip
                        b = CHAOS.corrupt("shuffle.fetch.corrupt", b)
                        parts.append(_BIN_BLOCK_HDR.pack(len(b), crc))
                        parts.append(b)
                    self.request.sendall(b"".join(parts))
                    return True
                header = json.loads(
                    _recv_exact(self.request, word, "control header",
                                self.client_address).decode("utf-8"))
                payload = _recv_exact(self.request,
                                      header.get("payload_len", 0),
                                      "control payload",
                                      self.client_address)
                self._dispatch(header, payload)
                return True

            def _dispatch(self, header: dict, payload: bytes = b"") -> None:
                # block fetches ride the binary framing exclusively
                # (_serve_one's BIN_FETCH path); no JSON fetch op exists
                op = header.get("op")
                if op == "list_blocks":
                    sid = header["shuffle_id"]
                    sizes, attempts, commits = outer.store.sizes_ex(
                        sid, header["partition"])
                    _send_msg(self.request, {
                        "sizes": sizes, "attempts": attempts,
                        "commits": commits,
                        "complete": outer.store.is_complete(sid)})
                elif op == "register" and outer.registry is not None:
                    outer.registry.register(header["executor_id"],
                                            header["host"], header["port"],
                                            header.get("role", "worker"))
                    _send_msg(self.request, {"ok": True})
                elif op == "new_shuffle" and outer.registry is not None:
                    _send_msg(self.request,
                              {"shuffle_id": outer.registry.next_shuffle_id()})
                elif op == "declare_shuffle" and outer.registry is not None:
                    outer.registry.declare_shuffle(header["shuffle_id"],
                                                   header["participants"])
                    _send_msg(self.request, {"ok": True})
                elif op == "join_shuffle" and outer.registry is not None:
                    outer.registry.join_shuffle(header["shuffle_id"],
                                                header["executor_id"])
                    _send_msg(self.request, {"ok": True})
                elif op == "map_complete" and outer.registry is not None:
                    won = outer.registry.map_complete(
                        header["shuffle_id"], header["executor_id"],
                        header.get("physical_id"))
                    _send_msg(self.request, {"ok": True, "won": won})
                elif op == "shuffle_status" and outer.registry is not None:
                    parts, comp, servers = outer.registry.shuffle_status(
                        header["shuffle_id"])
                    _send_msg(self.request,
                              {"participants": parts, "complete": comp,
                               "servers": servers})
                elif op == "replica_announce" and outer.registry is not None:
                    outer.registry.replica_announce(header["shuffle_id"],
                                                    header["src"],
                                                    header["holder"])
                    _send_msg(self.request, {"ok": True})
                elif op == "replica_holders" and outer.registry is not None:
                    _send_msg(self.request, {
                        "holders": outer.registry.replica_holders(
                            header["shuffle_id"], header["src"])})
                elif op == "catalog" and outer.registry is not None:
                    _send_msg(self.request, outer.registry.catalog())
                elif op == "leave" and outer.registry is not None:
                    left = outer.registry.leave(header["executor_id"])
                    _send_msg(self.request, {"ok": True, "left": left})
                elif op == "heartbeat" and outer.registry is not None:
                    # the beat optionally PIGGYBACKS the peer's latest
                    # resource sample (utils/telemetry.py) — no new RPC;
                    # legacy peers simply omit the field
                    outer.registry.heartbeat(header["executor_id"],
                                             header.get("telemetry"))
                    _send_msg(self.request,
                              {"peers": outer.registry.peers(
                                  workers_only=True)})
                elif op == "metrics":
                    # resource-plane scrape (tools/metrics_scrape.py):
                    # this node's sample + ring, plus — on the registry
                    # holder (the driver) — every rank's heartbeat ring
                    from spark_rapids_tpu.utils.telemetry import TELEMETRY
                    reply = {"local": TELEMETRY.local_metrics()}
                    if outer.registry is not None:
                        reply["ranks"] = outer.registry.rank_rings()
                    _send_msg(self.request, reply)
                elif op == "peer_failure" and outer.registry is not None:
                    excluded = outer.registry.report_failure(
                        header["executor_id"])
                    _send_msg(self.request, {"excluded": excluded})
                elif op == "put_replica":
                    # replica push: payload is the source partition's
                    # block list concatenated; lens/crcs (computed ONCE
                    # at the source's put) frame it back apart
                    blocks, off = [], 0
                    for ln, crc in zip(header["lens"], header["crcs"]):
                        blocks.append((payload[off:off + ln], int(crc)))
                        off += ln
                    outer.store.put_replica(
                        header["shuffle_id"], header["partition"],
                        header["src"], blocks,
                        attempts=header.get("attempts"),
                        commits=header.get("commits"))
                    _send_msg(self.request, {"ok": True})
                elif op == "replica_sizes":
                    sizes, attempts, commits = outer.store.replica_sizes_ex(
                        header["shuffle_id"], header["partition"],
                        header["src"])
                    _send_msg(self.request, {
                        "sizes": sizes, "attempts": attempts,
                        "commits": commits})
                elif op == "fetch_replica":
                    got = outer.store.get_replica_with_crcs(
                        header["shuffle_id"], header["partition"],
                        header["src"])
                    picked = [got[i] for i in header["blocks"]
                              if i < len(got)]
                    _send_msg(self.request,
                              {"lens": [len(b) for b, _ in picked],
                               "crcs": [crc for _, crc in picked]},
                              b"".join(b for b, _ in picked))
                elif op == "drop_query":
                    # query-teardown broadcast (driver failure path):
                    # drop the failed attempt's shuffles so the store
                    # can't leak them or satisfy a stale retry read
                    dropped = outer.store.drop_query(header["query_id"])
                    _send_msg(self.request, {"dropped": dropped})
                elif op == "cancel_query":
                    # cooperative-cancel broadcast (beside drop_query):
                    # flip every task token this node registered under
                    # the query id — running tasks stop at their next
                    # batch boundary / blessed wait (utils/cancel.py)
                    from spark_rapids_tpu.utils.cancel import CANCELS
                    n = CANCELS.cancel(
                        int(header["query_id"]),
                        header.get("reason") or "cancelled by driver")
                    _send_msg(self.request, {"cancelled": n})
                elif op == "store_info":
                    _send_msg(self.request,
                              {"shuffle_ids": outer.store.shuffle_ids()})
                else:
                    _send_msg(self.request, {"error": f"bad op {op}"})

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

        self._server = Server((host, port), Handler)
        self.addr = self._server.server_address
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()


# -- client side --------------------------------------------------------------

class PeerClient:
    """RPCs against one peer's block server (over the pooled, persistent
    per-peer connection).  ``executor_id`` is carried when known so
    failure reports can name the peer in the heartbeat registry."""

    def __init__(self, addr: Tuple[str, int],
                 executor_id: Optional[str] = None):
        self.addr = tuple(addr)
        self.executor_id = executor_id
        #: the LOGICAL slot this client reads (set by the transport's
        #: peer resolution): reads then select only that slot's committed
        #: blocks from the node's union list.  None = unfiltered legacy
        #: reads (standalone shuffles, diagnostics).
        self.serve_src: Optional[str] = None

    @property
    def conn(self) -> PooledConnection:
        return _POOL.get(self.addr)

    def list_blocks(self, shuffle_id: int, partition: int,
                    require_complete: bool = False) -> List[int]:
        h, _ = _request(self.addr, {"op": "list_blocks",
                                    "shuffle_id": shuffle_id,
                                    "partition": partition})
        if require_complete and not h.get("complete", False):
            raise RuntimeError(
                f"peer {self.addr} map output for shuffle {shuffle_id} "
                "not complete")
        return h["sizes"]

    def list_blocks_ex(self, shuffle_id: int, partition: int
                       ) -> Tuple[List[int], List[int], Dict[str, int]]:
        """(sizes, per-block attempt tags, {slot -> committed attempt})
        of the peer's primary list for this partition."""
        h, _ = _request(self.addr, {"op": "list_blocks",
                                    "shuffle_id": shuffle_id,
                                    "partition": partition})
        return (list(h["sizes"]),
                [int(a) for a in h.get("attempts", [0] * len(h["sizes"]))],
                {str(k): int(v) for k, v in h.get("commits", {}).items()})

    def new_shuffle_id(self) -> int:
        h, _ = _request(self.addr, {"op": "new_shuffle"})
        return h["shuffle_id"]

    def fetch_many(self, shuffle_id: int, partition: int,
                   blocks: List[int]) -> List[bytes]:
        """Binary hot path: all requested blocks in one round-trip."""
        return self.conn.fetch_many(shuffle_id, partition, list(blocks))

    def fetch_block(self, shuffle_id: int, partition: int,
                    block: int) -> bytes:
        # fetch_many raises PeerLostError itself when the block is missing
        return self.fetch_many(shuffle_id, partition, [block])[0]

    def register(self, executor_id: str, host: str, port: int,
                 role: str = "worker") -> None:
        _request(self.addr, {"op": "register", "executor_id": executor_id,
                             "host": host, "port": port, "role": role})

    def heartbeat(self, executor_id: str,
                  telemetry: Optional[dict] = None
                  ) -> Dict[str, Tuple[str, int]]:
        """Liveness beat, optionally piggybacking this node's latest
        resource sample (utils/telemetry.py) for the driver's per-rank
        telemetry rings — the continuous plane rides the EXISTING RPC."""
        header = {"op": "heartbeat", "executor_id": executor_id}
        if telemetry is not None:
            header["telemetry"] = telemetry
        h, _ = _request(self.addr, header)
        return {k: tuple(v) for k, v in h["peers"].items()}

    def metrics(self) -> dict:
        """This peer's resource-plane scrape payload (`metrics` op):
        {"local": {sample, ring}, "ranks": {eid: ring}} — ranks present
        only when the peer hosts the registry (the driver)."""
        h, _ = _request(self.addr, {"op": "metrics"})
        return h

    def join_shuffle(self, shuffle_id: int, executor_id: str) -> None:
        _request(self.addr, {"op": "join_shuffle", "shuffle_id": shuffle_id,
                             "executor_id": executor_id})

    def declare_shuffle(self, shuffle_id: int, participants) -> None:
        _request(self.addr, {"op": "declare_shuffle",
                             "shuffle_id": shuffle_id,
                             "participants": list(participants)})

    def map_complete(self, shuffle_id: int, executor_id: str,
                     physical_id: Optional[str] = None) -> bool:
        h, _ = _request(self.addr,
                        {"op": "map_complete", "shuffle_id": shuffle_id,
                         "executor_id": executor_id,
                         "physical_id": physical_id})
        return bool(h.get("won", True))

    def shuffle_status(self, shuffle_id: int
                       ) -> Tuple[List[str], List[str], Dict[str, str]]:
        h, _ = _request(self.addr, {"op": "shuffle_status",
                                    "shuffle_id": shuffle_id})
        return h["participants"], h["complete"], dict(h.get("servers", {}))

    def put_replica(self, shuffle_id: int, partition: int, src: str,
                    blocks: List[Tuple[bytes, int]],
                    attempts: Optional[List[int]] = None,
                    commits: Optional[Dict[str, int]] = None) -> None:
        """Push one partition's replicated block list to this holder
        (idempotent: replaces any previous copy).  ``attempts``/``commits``
        carry the source's block tags and slot commit-map snapshot so
        replica reads stay slot-filtered and staleness is detectable."""
        header = {"op": "put_replica", "shuffle_id": shuffle_id,
                  "partition": partition, "src": src,
                  "lens": [len(b) for b, _ in blocks],
                  "crcs": [crc for _, crc in blocks]}
        if attempts is not None:
            header["attempts"] = list(attempts)
        if commits is not None:
            header["commits"] = dict(commits)
        _request(self.addr, header, b"".join(b for b, _ in blocks))

    def replica_sizes(self, shuffle_id: int, partition: int,
                      src: str) -> List[int]:
        return self.replica_sizes_ex(shuffle_id, partition, src)[0]

    def replica_sizes_ex(self, shuffle_id: int, partition: int, src: str
                         ) -> Tuple[List[int], List[int], Dict[str, int]]:
        h, _ = _request(self.addr, {"op": "replica_sizes",
                                    "shuffle_id": shuffle_id,
                                    "partition": partition, "src": src})
        return (list(h["sizes"]),
                [int(a) for a in h.get("attempts", [0] * len(h["sizes"]))],
                {str(k): int(v) for k, v in h.get("commits", {}).items()})

    def fetch_replica(self, shuffle_id: int, partition: int, src: str,
                      blocks: List[int]) -> List[Tuple[bytes, int]]:
        h, payload = _request(self.addr,
                              {"op": "fetch_replica",
                               "shuffle_id": shuffle_id,
                               "partition": partition, "src": src,
                               "blocks": list(blocks)})
        out, off = [], 0
        for ln, crc in zip(h["lens"], h["crcs"]):
            out.append((payload[off:off + ln], int(crc)))
            off += ln
        return out

    def replica_announce(self, shuffle_id: int, src: str,
                         holder: str) -> None:
        _request(self.addr, {"op": "replica_announce",
                             "shuffle_id": shuffle_id, "src": src,
                             "holder": holder})

    def replica_holders(self, shuffle_id: int, src: str) -> List[str]:
        h, _ = _request(self.addr, {"op": "replica_holders",
                                    "shuffle_id": shuffle_id, "src": src})
        return [str(x) for x in h.get("holders", [])]

    def catalog(self) -> dict:
        h, _ = _request(self.addr, {"op": "catalog"})
        return h

    def leave(self, executor_id: str) -> bool:
        h, _ = _request(self.addr, {"op": "leave",
                                    "executor_id": executor_id})
        return bool(h.get("left", False))

    def report_peer_failure(self, executor_id: str) -> bool:
        """Tell this registry host that ``executor_id`` keeps failing
        fetches; returns True when the registry excluded it."""
        h, _ = _request(self.addr, {"op": "peer_failure",
                                    "executor_id": executor_id})
        return bool(h.get("excluded", False))

    def drop_query(self, query_id: int) -> int:
        """Drop every shuffle of a cluster query from this peer's block
        store; returns the number of shuffles dropped."""
        h, _ = _request(self.addr, {"op": "drop_query",
                                    "query_id": int(query_id)})
        return int(h.get("dropped", 0))

    def cancel_query(self, query_id: int, reason: str = "") -> int:
        """Cooperatively cancel the query's running tasks on this peer
        (flips its registered CancelTokens); returns how many tokens
        transitioned to cancelled."""
        h, _ = _request(self.addr, {"op": "cancel_query",
                                    "query_id": int(query_id),
                                    "reason": reason})
        return int(h.get("cancelled", 0))

    def store_info(self) -> List[int]:
        """Shuffle ids currently resident in this peer's block store
        (diagnostics + the leak-regression tests)."""
        h, _ = _request(self.addr, {"op": "store_info"})
        return [int(s) for s in h.get("shuffle_ids", [])]


class ReplicaClient:
    """Duck-typed peer serving ``src``'s replicated map output from its
    holder set (the failover target when the primary is lost or serves
    persistently corrupt frames).  Block indices and order match the
    source's primary list — replication copies whole partition lists —
    so a reader can swap this in mid-partition and keep its indices.

    Holders are tried in order; each fetched frame verifies against the
    CRC computed at the SOURCE's put (replication never recomputes), so
    a corrupt replica fails over to the next holder rather than serving
    wrong bytes."""

    def __init__(self, src: str, holders: List[Tuple[str, Tuple[str, int]]]):
        self.src = str(src)
        self.holders = list(holders)          # [(holder eid, addr)]
        self.executor_id = f"replica<{self.src}>"
        self.addr = self.holders[0][1] if self.holders else ("?", 0)
        #: logical slot the reader selects (same contract as PeerClient)
        self.serve_src: Optional[str] = None

    def _try_each(self, fn, what: str):
        last: Optional[BaseException] = None
        for eid, addr in self.holders:
            try:
                return fn(PeerClient(addr, executor_id=eid))
            except (OSError, RetryBudgetExhausted) as e:
                last = e
        raise PeerLostError(
            f"no replica holder of {self.src} could serve {what} "
            f"(tried {[eid for eid, _ in self.holders]})") from last

    def list_blocks(self, shuffle_id: int, partition: int,
                    require_complete: bool = False) -> List[int]:
        def go(peer: PeerClient):
            sizes = peer.replica_sizes(shuffle_id, partition, self.src)
            return sizes
        return self._try_each(
            go, f"replica sizes of shuffle {shuffle_id} "
                f"partition {partition}")

    def list_blocks_ex(self, shuffle_id: int, partition: int
                       ) -> Tuple[List[int], List[int], Dict[str, int]]:
        def go(peer: PeerClient):
            return peer.replica_sizes_ex(shuffle_id, partition, self.src)
        return self._try_each(
            go, f"replica listing of shuffle {shuffle_id} "
                f"partition {partition}")

    def fetch_many(self, shuffle_id: int, partition: int,
                   blocks: List[int]) -> List[bytes]:
        want = list(blocks)

        def go(peer: PeerClient):
            got = peer.fetch_replica(shuffle_id, partition, self.src, want)
            if len(got) != len(want):
                raise PeerLostError(
                    f"replica holder {peer.addr} has "
                    f"{len(got)}/{len(want)} blocks of {self.src}'s "
                    f"shuffle {shuffle_id} partition {partition}")
            if checksum_enabled():
                bad = [i for i, (b, crc) in enumerate(got)
                       if not verify_frame(b, crc)]
                SHUFFLE_COUNTERS.add(
                    checksums_verified=sum(1 for _, crc in got if crc))
                if bad:
                    SHUFFLE_COUNTERS.add(checksum_failures=len(bad))
                    raise BlockCorruptionError(
                        f"checksum mismatch on replica block(s) {bad} of "
                        f"{self.src}'s shuffle {shuffle_id} partition "
                        f"{partition} from holder {peer.addr}")
            return [b for b, _ in got]

        def attempt(peer: PeerClient):
            # one corruption retry per holder, then the next holder
            try:
                return go(peer)
            except BlockCorruptionError:
                SHUFFLE_COUNTERS.add(blocks_refetched=len(want))
                return go(peer)

        out = self._try_each(
            attempt, f"shuffle {shuffle_id} partition {partition} "
                     f"blocks {want}")
        SHUFFLE_COUNTERS.add(blocks_refetched_replica=len(out),
                             bytes_fetched=sum(len(b) for b in out),
                             fetch_requests=1, blocks_fetched=len(out))
        return out


class BlockFetchIterator:
    """Pull all of a partition's blocks from a set of peers under a bounded
    in-flight byte budget (the reference's receive-side throttle:
    RapidsShuffleIterator + BufferReceiveState bounce buffers).

    PIPELINED: one background prefetch thread per peer streams that peer's
    blocks through ``fetch_many`` (multiple blocks per round-trip, up to
    ``request_bytes`` each), filling a shared queue bounded by
    ``max_inflight_bytes`` of fetched-but-unconsumed data.  The consumer
    pops in arrival order, so network fetch runs CONCURRENTLY with
    whatever device compute the consumer interleaves — the fetch/compute
    overlap the reference gets from BufferReceiveState's async transfers.
    Consumer wait time on an empty queue is recorded as prefetch stall."""

    def __init__(self, peers: List[PeerClient], shuffle_id: int,
                 partition: int, max_inflight_bytes: int = 64 << 20,
                 fetch_threads: int = 4, request_bytes: int = 4 << 20,
                 report_failure=None, replica_resolver=None):
        self.peers = peers
        self.shuffle_id = shuffle_id
        self.partition = partition
        self.max_inflight = max(int(max_inflight_bytes), 1)
        #: cap on CONCURRENT fetch round-trips across peers (one prefetch
        #: thread per peer, but at most this many in a request at once)
        self.fetch_threads = max(int(fetch_threads), 1)
        self.request_bytes = max(int(request_bytes), 1)
        #: callable(peer) invoked when a peer exhausts its fetch budget
        #: (the transport reports it to the heartbeat registry so
        #: repeat offenders get excluded)
        self.report_failure = report_failure
        #: callable(peer) -> Optional[ReplicaClient]: where this peer's
        #: map output can be re-fetched from if the peer itself cannot
        #: serve it (replication failover — re-fetch, not re-execute)
        self.replica_resolver = replica_resolver

    def _slot_pairs(self, peer) -> Optional[List[Tuple[int, int]]]:
        """(index, size) pairs of the blocks ``peer`` serves for the
        reader's slot, out of the node's (or replica record's) union
        listing.  ``peer.serve_src`` None means unfiltered legacy reads.
        None return: the listing has NO commit record for the slot — a
        replica snapshot that predates the slot's commit, or a restarted
        node that lost it — the caller must escalate, never under-serve."""
        sizes, attempts, commits = peer.list_blocks_ex(self.shuffle_id,
                                                       self.partition)
        slot = getattr(peer, "serve_src", None)
        if slot is None:
            return list(enumerate(sizes))
        att = commits.get(slot)
        if att is None:
            return None
        return [(i, s) for i, (s, a) in enumerate(zip(sizes, attempts))
                if a == att]

    def _require_pairs(self, peer) -> List[Tuple[int, int]]:
        pairs = self._slot_pairs(peer)
        if pairs is None:
            raise PeerLostError(
                f"{peer.executor_id or peer.addr} has no commit record "
                f"for slot {getattr(peer, 'serve_src', None)} of shuffle "
                f"{self.shuffle_id} (stale or restarted copy)")
        return pairs

    def _failover(self, peer):
        """Resolve the replica standing in for ``peer``'s slot, with the
        slot's pair listing — or re-raise the active error when none
        exists (escalation to scoped recovery)."""
        if self.report_failure is not None:
            self.report_failure(peer)
        replica = (self.replica_resolver(peer)
                   if self.replica_resolver is not None
                   and not isinstance(peer, ReplicaClient) else None)
        if replica is None:
            raise
        replica.serve_src = getattr(peer, "serve_src", None)
        pairs = self._require_pairs(replica)
        SHUFFLE_COUNTERS.add(replica_failovers=1)
        return replica, pairs

    def _fetch_batch(self, state: dict, take: List[int]) -> List[bytes]:
        """One batch round-trip (``take`` is slot-ORDINAL positions into
        ``state['pairs']``) with CORRUPTION recovery: a checksum mismatch
        re-fetches the batch from the serving peer under a bounded budget
        (transport errors already retry inside the pooled connection's
        own budget).  When the peer cannot serve at all (budget dry, map
        output gone) and a replica exists, the worker PERMANENTLY
        switches to it — ordinals re-resolve against the replica's OWN
        listing, so index drift between snapshots cannot mis-address
        blocks — and escalation to the scoped re-execution path happens
        only with no usable replica left.  Budget exhaustion and lost
        map output report the peer before failing over."""
        peer = state["peer"]
        CHAOS.delay("shuffle.fetch.delay")
        budget = network_budget(
            f"shuffle.fetch:{self.shuffle_id}/{self.partition}"
            f"@{peer.addr[0]}:{peer.addr[1]}")
        idxs = [state["pairs"][o][0] for o in take]
        try:
            while True:
                try:
                    return peer.fetch_many(self.shuffle_id,
                                           self.partition, idxs)
                except BlockCorruptionError as e:
                    budget.backoff(error=e)  # RetryBudgetExhausted if dry
                    SHUFFLE_COUNTERS.add(blocks_refetched=len(take))
        except (RetryBudgetExhausted, PeerLostError):
            replica, pairs = self._failover(peer)
            if len(pairs) != len(state["pairs"]):
                raise PeerLostError(
                    f"replica of slot {getattr(peer, 'serve_src', None)} "
                    f"serves {len(pairs)} blocks where the primary "
                    f"served {len(state['pairs'])} (inconsistent copy)")
            state["peer"], state["pairs"] = replica, pairs
            return replica.fetch_many(self.shuffle_id, self.partition,
                                      [pairs[o][0] for o in take])

    def __iter__(self):
        import collections

        from spark_rapids_tpu.utils.cancel import (cancellable_wait,
                                                   current_cancel_token)
        # the consumer's ambient token governs the whole read: workers
        # are plain threads (no ambient of their own), so they observe
        # the SAME token explicitly — a cancelled query's fetch plane
        # stops fetching instead of draining the partition
        token = current_cancel_token()
        sources = []                # [{"peer": ..., "pairs": [(idx, sz)]}]
        for peer in self.peers:
            try:
                sources.append({"peer": peer,
                                "pairs": self._require_pairs(peer)})
            except OSError:
                # the peer's reconnect budget ran dry (or its commit
                # record is gone) before the read even started: report
                # it, then serve the slot from a replica when one exists
                replica, pairs = self._failover(peer)
                sources.append({"peer": replica, "pairs": pairs})
        if not any(s["pairs"] for s in sources):
            return
        cv = threading.Condition()
        queue: "collections.deque[bytes]" = collections.deque()
        state = {"inflight": 0, "live_workers": 0, "error": None,
                 "stopped": False}

        # a round-trip's batch may not exceed the flow-control window —
        # otherwise one fetch_many could hold more than max_inflight bytes
        batch_budget = min(self.request_bytes, self.max_inflight)
        # spark.rapids.shuffle.fetch.threads: bound on concurrent
        # round-trips (acquired per request, so a stalled peer holds at
        # most one slot)
        request_slots = threading.BoundedSemaphore(self.fetch_threads)

        def worker(src_state: dict) -> None:
            try:
                # ordinals index src_state["pairs"] — _fetch_batch may
                # swap in a replica (re-resolving indices) mid-iteration
                sizes = [s for _, s in src_state["pairs"]]
                i = 0
                while i < len(sizes):
                    # batch blocks into one round-trip up to the budget
                    take, batch_bytes = [i], sizes[i]
                    i += 1
                    while (i < len(sizes)
                           and batch_bytes + sizes[i] <= batch_budget):
                        take.append(i)
                        batch_bytes += sizes[i]
                        i += 1
                    with cv:
                        # window: wait for room; an oversized batch may
                        # proceed alone so progress is always possible
                        cancellable_wait(
                            cv,
                            predicate=lambda: not (
                                state["inflight"] > 0
                                and state["inflight"] + batch_bytes
                                > self.max_inflight
                                and not state["stopped"]),
                            token=token, site="shuffle.fetch.window")
                        if state["stopped"]:
                            return
                        state["inflight"] += batch_bytes
                        # resource-plane gauge (utils/telemetry.py):
                        # process-wide fetched-but-unconsumed bytes,
                        # one add per round-trip batch
                        from spark_rapids_tpu.utils.telemetry import \
                            FETCH_INFLIGHT
                        FETCH_INFLIGHT.add(batch_bytes)
                    with request_slots:
                        got = self._fetch_batch(src_state, take)
                    with cv:
                        queue.extend(got)
                        cv.notify_all()
            except BaseException as e:  # noqa: BLE001 — surfaced to consumer
                with cv:
                    if state["error"] is None:
                        state["error"] = e
                    cv.notify_all()
            finally:
                with cv:
                    state["live_workers"] -= 1
                    cv.notify_all()

        from spark_rapids_tpu.utils.ambient import (Ambients,
                                                    spawn_with_ambients)
        # fetch workers act for the consuming reduce task: same tenant,
        # priority and cancel token; captured ONCE, on the consumer's
        # thread
        amb = Ambients.capture()
        threads = []
        with cv:
            for src_state in sources:
                if not src_state["pairs"]:
                    continue
                state["live_workers"] += 1
                t = spawn_with_ambients(worker, src_state, start=False,
                                        ambients=amb)
                threads.append(t)
        for t in threads:
            t.start()
        try:
            while True:
                with cv:
                    t0 = time.perf_counter_ns()
                    cancellable_wait(
                        cv,
                        predicate=lambda: (queue
                                           or state["live_workers"] <= 0
                                           or state["error"] is not None),
                        token=token, site="shuffle.fetch.drain")
                    stall_ns = time.perf_counter_ns() - t0
                    err = state["error"]
                    block = None
                    if err is None and queue:
                        block = queue.popleft()
                        state["inflight"] -= len(block)
                        from spark_rapids_tpu.utils.telemetry import \
                            FETCH_INFLIGHT
                        FETCH_INFLIGHT.add(-len(block))
                        cv.notify_all()
                # stall accounting outside cv: the counter add takes the
                # process-wide stats lock, which must never nest under
                # the fetch condition
                SHUFFLE_COUNTERS.add(prefetch_stall_ns=stall_ns)
                if stall_ns:
                    # per-stage fetch-wait latency distribution: the tail
                    # of these stalls is what the fleet-scale SLO story
                    # needs visible (shuffle/stats.py Histogram)
                    from spark_rapids_tpu.shuffle.stats import HISTOGRAMS
                    HISTOGRAMS["fetch_wait_s"].record(stall_ns / 1e9)
                if err is not None:
                    raise err
                if block is None:
                    return          # all workers drained
                yield block         # outside the lock: consumer compute
                                    # overlaps the workers' next fetches
        finally:
            with cv:
                state["stopped"] = True
                # an abandoned read's residual in-flight bytes leave the
                # process gauge (workers observe stopped before adding
                # more, so the final adjustment cannot race an add)
                from spark_rapids_tpu.utils.telemetry import \
                    FETCH_INFLIGHT
                FETCH_INFLIGHT.add(-state["inflight"])
                state["inflight"] = 0
                cv.notify_all()


# -- SPI implementation -------------------------------------------------------

class TcpShuffleTransport:
    """ShuffleTransport over the block server: the MULTIPROCESS mode.

    One instance per exchange; `executor` carries the process-wide node
    state (store, server, peer set).  Shuffle ids come from the driver
    registry so every host names the same exchange identically."""

    def __init__(self, executor: "ShuffleExecutor", num_partitions: int,
                 schema: Schema, codec: str = "none",
                 max_inflight_bytes: int = 64 << 20,
                 fetch_threads: int = 4,
                 merge_chunk_bytes: int = 32 << 20,
                 shuffle_id: Optional[int] = None,
                 completeness_timeout_s: float = 120.0,
                 participants=None,
                 request_bytes: int = 4 << 20,
                 attempt: int = 0,
                 logical_id: Optional[str] = None,
                 replication: int = 1,
                 persist_dir: str = ""):
        self.shuffle_id = (shuffle_id if shuffle_id is not None
                           else executor.new_shuffle_id())
        self.executor = executor
        self.num_partitions = num_partitions
        self.schema = schema
        self.codec = codec
        self.max_inflight = max_inflight_bytes
        self.fetch_threads = fetch_threads
        self.merge_chunk_bytes = max(int(merge_chunk_bytes), 1)
        self.request_bytes = max(int(request_bytes), 1)
        self.completeness_timeout_s = completeness_timeout_s
        #: task attempt writing this shuffle (speculation/re-dispatch);
        #: tags blocks in the store so a lost first-commit race drops
        #: exactly this attempt's output
        self.attempt = int(attempt)
        #: the LOGICAL participant slot this task fills (its own id
        #: unless it is a speculative copy / re-dispatch of another
        #: executor's rank)
        self.logical_id = logical_id or executor.executor_id
        #: replication factor k: after the map commit wins, blocks are
        #: pushed asynchronously to k-1 rendezvous-chosen peers
        self.replication = max(int(replication), 1)
        if persist_dir:
            executor.store.set_persist_dir(persist_dir)
        # declare map-side participation up front: readers only await
        # completeness from executors that actually participate in this
        # shuffle, so a registered-but-idle worker never stalls reads
        # (ADVICE r2 #5).  A coordinator that knows the full worker set
        # passes `participants` so a reader racing a slow worker's
        # transport construction still waits for it.
        self.executor.join_shuffle(self.shuffle_id, as_id=self.logical_id)
        if participants:
            self.executor.declare_shuffle(self.shuffle_id, participants)

    supports_range_write = True

    def _commit_map(self) -> None:
        """Commit this attempt's map output: FIRST COMMIT WINS at the
        registry.  A win replicates the blocks to k-1 peers (async — the
        reduce phase overlaps the push); a loss means another attempt
        already serves this logical slot, so this attempt's blocks are
        dropped by attempt id (serving both copies would double every
        reduce row)."""
        # record slot -> attempt BEFORE the registry win is visible, so
        # a reader that sees the commit always finds the serving record
        self.executor.store.note_commit(self.shuffle_id, self.logical_id,
                                        self.attempt)
        self.executor.store.mark_complete(self.shuffle_id)
        won = self.executor.map_complete(self.shuffle_id,
                                         as_id=self.logical_id)
        if not won:
            SHUFFLE_COUNTERS.add(map_commits_lost=1)
            self.executor.store.drop_commit(self.shuffle_id,
                                            self.logical_id)
            self.executor.store.drop_shuffle_attempt(self.shuffle_id,
                                                     self.attempt)
            return
        SHUFFLE_COUNTERS.add(map_commits_won=1)
        if self.replication > 1:
            self.executor.replicate_shuffle_async(
                self.shuffle_id, self.replication,
                src=self.logical_id)

    def write(self, pieces: Iterable[Tuple[int, ColumnarBatch]]) -> None:
        from spark_rapids_tpu.shuffle.serializer import serialize_batch
        for p, piece in pieces:
            self.executor.store.put(self.shuffle_id, p,
                                    serialize_batch(piece, self.codec),
                                    attempt=self.attempt)
        self._commit_map()

    def write_batches(self, batches) -> None:
        """Range write (MULTIPROCESS): every partition's wire block is
        framed from row ranges of one downloaded map batch; map-side CRC
        is still computed once per block at BlockStore.put."""
        from spark_rapids_tpu.shuffle.serializer import serialize_batch_ranges
        for host_batch, host_counts in batches:
            blocks = serialize_batch_ranges(host_batch, host_counts,
                                            self.codec)
            for p, block in enumerate(blocks):
                if block is not None:
                    self.executor.store.put(self.shuffle_id, p, block,
                                            attempt=self.attempt)
        self._commit_map()

    def _await_and_resolve_peers(self) -> List[PeerClient]:
        """Wait for every declared participant's map completion, then
        resolve reachable peer clients (excluding self).  The wait is a
        named ``RetryBudget`` deadline (unlimited polls, bounded delay):
        a lost participant surfaces as a budget error naming the shuffle
        and the pending executors, never a silent hang.

        Resolution goes through the registry's SERVING MAP (logical
        participant -> physical committer: first-commit-wins under
        speculation/re-dispatch).  A committed slot whose server is
        unreachable resolves to its REPLICA holders when the catalog has
        any — executor loss then costs a re-fetch, not a re-execution;
        only a slot with no surviving copy escalates to PeerLostError
        (the scoped-recovery path)."""
        from spark_rapids_tpu.utils.cancel import (check_cancelled,
                                                   current_cancel_token)
        from spark_rapids_tpu.utils.watchdog import WATCHDOG
        self.executor.heartbeat()
        budget = RetryBudget(
            f"shuffle.completeness:{self.shuffle_id}",
            max_attempts=None, base_delay_s=0.02, max_delay_s=0.25,
            deadline_s=self.completeness_timeout_s)
        with WATCHDOG.waiting("shuffle.completeness",
                              current_cancel_token()):
            while True:
                # cancellation point: a cancelled query must not sit out
                # the completeness timeout waiting for map output that
                # will never commit (its writers were cancelled too)
                check_cancelled()
                participants, complete, servers = \
                    self.executor.shuffle_status(self.shuffle_id)
                if set(participants) <= set(complete):
                    break
                pending = RuntimeError(
                    f"shuffle {self.shuffle_id}: map output incomplete: "
                    f"{sorted(set(participants) - set(complete))} pending")
                budget.backoff(error=pending)  # exhaustion names budget
        # re-learn peers AFTER the wait: a participant may have registered
        # while we were waiting for map output
        self.executor.heartbeat()
        remote = []
        for logical in complete:
            physical = servers.get(logical, logical)
            if physical == self.executor.executor_id:
                continue        # served by the local store
            # ONE slot-filtered client per logical participant: a node
            # serving several slots (it adopted a lost/straggling rank)
            # gets one client per slot, each selecting only that slot's
            # committed blocks from the union listing — slots can never
            # double-serve or under-serve each other
            peer = self.executor.peer_client_for(physical)
            if peer is None:
                # committed but unreachable: re-fetch from replicas when
                # any were announced; only a slot with NO surviving copy
                # escalates (fetch-failed -> scoped recompute is the
                # upper layer's job, as in Spark).  Replicas are cataloged
                # under the pushing slot's id — usually the logical slot,
                # but a drain of standalone blocks announces under the
                # holder's physical id, so try both.
                peer = (self.executor.replica_client_for(self.shuffle_id,
                                                         logical)
                        or (self.executor.replica_client_for(
                            self.shuffle_id, physical)
                            if physical != logical else None))
                if peer is None:
                    raise PeerLostError(
                        f"shuffle {self.shuffle_id}: completed "
                        f"participant {logical} (server {physical}) has "
                        "no reachable address and no replicas "
                        "(peer lost)")
                SHUFFLE_COUNTERS.add(replica_failovers=1)
            peer.serve_src = logical
            remote.append(peer)
        return remote

    def read_iter(self, partition: int, target_rows: Optional[int] = None):
        """STREAMING reduce read with CONCAT-ONCE merge: own blocks
        short-circuit through the in-process store; remote blocks arrive
        through the pipelined per-peer prefetch (bounded in-flight bytes)
        and accumulate as RAW wire buffers until a flush boundary, then
        materialize with a SINGLE merge_batches call — one HBM upload and
        one canonicalize per reduce partition in the common case, instead
        of a per-fetch merge+concat chain.  Flush boundaries: every
        `merge_chunk_bytes` of wire data (the VERDICT r4 #7 memory bound:
        resident memory stays window + chunk at any fan-in), and — when
        the wire headers are readable — every `target_rows` rows, so
        merged batches land on the consumer's coalesce target and the
        exchange exec never re-concats them.  Reference:
        BufferSendState.scala / WindowedBlockIterator.scala."""
        from spark_rapids_tpu.memory.retry import with_retry_no_split
        from spark_rapids_tpu.shuffle.serializer import (
            merge_batches, wire_row_count)
        remote = self._await_and_resolve_peers()

        def resolve_replica(peer):
            # replicas are cataloged under the pushing slot's id; the
            # holder's physical id covers drained standalone blocks
            for src in dict.fromkeys(
                    [getattr(peer, "serve_src", None) or peer.executor_id,
                     peer.executor_id]):
                replica = self.executor.replica_client_for(
                    self.shuffle_id, src)
                if replica is not None:
                    return replica
            return None

        def wire_blocks():
            # local short-circuit serves every slot THIS node committed
            # (own rank + adopted wins), never an uncommitted attempt's
            yield from self.executor.store.get_committed(self.shuffle_id,
                                                         partition)
            if remote:
                yield from BlockFetchIterator(
                    remote, self.shuffle_id, partition, self.max_inflight,
                    fetch_threads=self.fetch_threads,
                    request_bytes=self.request_bytes,
                    report_failure=self.executor.report_peer_failure,
                    replica_resolver=resolve_replica)

        chunk: List[bytes] = []
        acc = 0
        rows = 0                 # None once a block's row count is opaque
        for raw in wire_blocks():
            chunk.append(raw)
            acc += len(raw)
            if rows is not None and target_rows:
                rc = wire_row_count(raw)
                rows = None if rc is None else rows + rc
            if acc >= self.merge_chunk_bytes or (
                    target_rows and rows is not None
                    and rows >= target_rows):
                # under retry: the merge is THE reduce-side HBM upload;
                # its inputs are host wire bytes, so a spill-and-rerun
                # is safe and an OOM here must not fail the query
                out = with_retry_no_split(
                    lambda: merge_batches(chunk, self.schema))
                chunk, acc, rows = [], 0, 0
                if out is not None:
                    yield out
        if chunk:
            out = with_retry_no_split(
                lambda: merge_batches(chunk, self.schema))
            if out is not None:
                yield out

    def read_pieces(self, partition: int,
                    target_rows: Optional[int] = None):
        """Piece stream for the fused reduce path: the flow-controlled
        fetch + merge already bounds and uploads here, so pieces are the
        merged device batches (the fused program still folds its concat
        and compute into one launch per coalesced group)."""
        from spark_rapids_tpu.shuffle.transport import StreamPiece
        for b in self.read_iter(partition, target_rows=target_rows):
            yield StreamPiece.of_batch(b)

    def read(self, partition: int) -> List[ColumnarBatch]:
        return list(self.read_iter(partition))

    def cleanup(self) -> None:
        self.executor.store.drop_shuffle(self.shuffle_id)


class ShuffleExecutor:
    """Process-wide shuffle node: local store + block server + membership.

    Standalone (single-node) construction needs no driver; multi-host
    construction registers with the driver's registry address and
    discovers peers via heartbeats."""

    def __init__(self, executor_id: Optional[str] = None,
                 driver_addr: Optional[Tuple[str, int]] = None,
                 serve_registry: bool = False, host: str = "127.0.0.1",
                 role: str = "worker",
                 persist_dir: Optional[str] = None):
        self.executor_id = executor_id or f"exec-{os.getpid()}"
        self.role = role
        self.store = BlockStore(persist_dir=persist_dir)
        self.registry = HeartbeatRegistry() if serve_registry else None
        self.server = ShuffleBlockServer(self.store, self.registry,
                                         host=host)
        self._peers: Dict[str, Tuple[str, int]] = {
            self.executor_id: self.server.addr}
        self._driver = driver_addr
        #: in-flight async replication pushes: sid -> Event set when the
        #: push (and its catalog announcements) finished
        self._repl_lock = threading.Lock()
        #: (shuffle_id, src) -> done event for an async replica push
        self._repl_done: Dict[Tuple[int, str], threading.Event] = {}
        #: shuffle/replica catalog snapshot pulled at registration (a
        #: joiner's warm view; live lookups still go to the registry)
        self._catalog: dict = {}
        if driver_addr is not None:
            PeerClient(driver_addr).register(
                self.executor_id, self.server.addr[0], self.server.addr[1],
                role=role)
            self.heartbeat()
            self.sync_catalog()
        elif self.registry is not None:
            self.registry.register(self.executor_id, *self.server.addr,
                                   role=role)

    def heartbeat(self) -> None:
        """Refresh liveness + REPLACE the peer view (executorHeartbeat).
        Replacing (rather than merging) drops peers the registry has timed
        out, so one crashed worker doesn't poison every later read."""
        if self._driver is not None:
            # piggyback the latest resource sample (None while the
            # sampler is disabled or hasn't ticked — the wire shape is
            # then exactly the legacy beat)
            from spark_rapids_tpu.utils.telemetry import TELEMETRY
            peers = PeerClient(self._driver).heartbeat(
                self.executor_id, telemetry=TELEMETRY.latest())
        elif self.registry is not None:
            peers = dict(self.registry.peers(workers_only=True))
        else:
            return
        peers[self.executor_id] = self.server.addr
        self._peers = peers

    def peer_clients(self, include_self: bool = True) -> List[PeerClient]:
        return [PeerClient(addr, executor_id=eid)
                for eid, addr in self._peers.items()
                if include_self or eid != self.executor_id]

    def report_peer_failure(self, peer) -> None:
        """A fetch against ``peer`` exhausted its budget: report it to
        the heartbeat registry (driver-hosted when remote) so repeat
        offenders are excluded from later reads.  Best-effort — the
        registry may itself be unreachable while things are on fire."""
        eid = getattr(peer, "executor_id", None) or str(peer)
        try:
            if self._driver is not None:
                PeerClient(self._driver).report_peer_failure(eid)
            elif self.registry is not None:
                self.registry.report_failure(eid)
        except OSError:
            pass  # best-effort: the fetch error itself still escalates

    def new_shuffle_id(self) -> int:
        """Driver-coordinated when remote; registry-local standalone."""
        if self._driver is not None:
            return PeerClient(self._driver).new_shuffle_id()
        assert self.registry is not None
        return self.registry.next_shuffle_id()

    def join_shuffle(self, shuffle_id: int,
                     as_id: Optional[str] = None) -> None:
        logical = as_id or self.executor_id
        if self._driver is not None:
            PeerClient(self._driver).join_shuffle(shuffle_id, logical)
        elif self.registry is not None:
            self.registry.join_shuffle(shuffle_id, logical)

    def declare_shuffle(self, shuffle_id: int, participants) -> None:
        if self._driver is not None:
            PeerClient(self._driver).declare_shuffle(shuffle_id,
                                                     participants)
        elif self.registry is not None:
            self.registry.declare_shuffle(shuffle_id, participants)

    def map_complete(self, shuffle_id: int,
                     as_id: Optional[str] = None) -> bool:
        """Commit map output for the logical slot ``as_id`` (default:
        self), served by THIS executor.  Returns whether the commit won
        (first-commit-wins under speculation/re-dispatch)."""
        logical = as_id or self.executor_id
        if self._driver is not None:
            return PeerClient(self._driver).map_complete(
                shuffle_id, logical, physical_id=self.executor_id)
        if self.registry is not None:
            return self.registry.map_complete(
                shuffle_id, logical, physical_id=self.executor_id)
        return True

    def shuffle_status(self, shuffle_id: int):
        if self._driver is not None:
            return PeerClient(self._driver).shuffle_status(shuffle_id)
        if self.registry is not None:
            return self.registry.shuffle_status(shuffle_id)
        return ([self.executor_id], [self.executor_id],
                {self.executor_id: self.executor_id})

    def peer_client_for(self, executor_id: str) -> Optional[PeerClient]:
        addr = self._peers.get(executor_id)
        return (PeerClient(addr, executor_id=executor_id)
                if addr is not None else None)

    # -- durability: replication + catalog ------------------------------------

    def _rendezvous_targets(self, shuffle_id: int, src: str,
                            k: int) -> List[str]:
        """The k-1 replica holders for (shuffle, src): highest rendezvous
        hash over the live worker set excluding self.  Every node ranks
        peers identically, so holders are discoverable by recomputation
        as well as through the registry catalog."""
        import hashlib
        candidates = [eid for eid in self._peers
                      if eid != self.executor_id]
        candidates.sort(
            key=lambda eid: hashlib.md5(
                f"{shuffle_id}:{src}:{eid}".encode()).hexdigest(),
            reverse=True)
        return candidates[:max(k - 1, 0)]

    def replicate_shuffle(self, shuffle_id: int, k: int,
                          src: Optional[str] = None,
                          drain: bool = False) -> int:
        """Push every partition's committed block list for ``shuffle_id``
        to k-1 rendezvous-chosen peers and announce them in the
        registry's replica catalog.  Idempotent (put_replica replaces).
        Returns the UNIQUE blocks secured (pushed to at least one
        holder); ``drain=True`` counts them as drained (graceful-leave
        accounting) instead of per-copy replicated."""
        src = src or self.executor_id
        targets = self._rendezvous_targets(shuffle_id, src, k)
        if not targets:
            return 0
        # snapshot once, filtered to the SLOT's committed attempt when
        # one is recorded (a node may hold several slots' blocks for one
        # shuffle — each slot replicates its own blocks under its own
        # src, so replica records stay disjoint and indexable); with no
        # commit record (standalone blocks in a drain) the whole list
        # goes under the caller's src
        commits = self.store.commits(shuffle_id)
        att = commits.get(str(src))
        parts: Dict[int, List[Tuple[bytes, int, int]]] = {}
        for p in self.store.partitions(shuffle_id):
            entries = self.store.get_entries(shuffle_id, p)
            if att is not None:
                entries = [t for t in entries if t[2] == att]
            if entries:
                parts[p] = entries
        snap = {str(src): att} if att is not None else dict(commits)
        total_blocks = sum(len(e) for e in parts.values())
        ok_targets = 0
        for eid in targets:
            peer = self.peer_client_for(eid)
            if peer is None:
                continue
            try:
                for p, entries in sorted(parts.items()):
                    peer.put_replica(
                        shuffle_id, p, src,
                        [(b, crc) for b, crc, _ in entries],
                        attempts=[a for _, _, a in entries],
                        commits=snap)
                    if not drain:
                        # replicated counters are PER COPY (fan-out cost)
                        SHUFFLE_COUNTERS.add(
                            blocks_replicated=len(entries),
                            bytes_replicated=sum(len(b)
                                                 for b, _, _ in entries))
                self.replica_announce(shuffle_id, src, eid)
                ok_targets += 1
            except OSError:
                # best-effort: a holder that died mid-push just isn't
                # announced; the remaining copies still protect the data
                continue
        if drain and ok_targets:
            # drained counts UNIQUE primary blocks secured (>=1 copy),
            # not copies — factor>=3 must not multi-count the drain
            SHUFFLE_COUNTERS.add(blocks_drained=total_blocks)
        return total_blocks if ok_targets else 0

    def replicate_shuffle_async(self, shuffle_id: int, k: int,
                                src: Optional[str] = None) -> None:
        """Asynchronous replication: the reduce phase (and the task's
        result push) overlap the replica push.  ``wait_replicated`` joins
        it — graceful leave and deterministic tests need the blocks
        durable before the node may die.  Deduped per (shuffle, SOURCE):
        a node serving two logical slots of one shuffle (it adopted a
        lost rank) must push and announce under BOTH srcs — deduping by
        shuffle id alone would silently skip the adopted slot's copy."""
        key = (int(shuffle_id), str(src or self.executor_id))
        with self._repl_lock:
            ev = self._repl_done.get(key)
            if ev is not None and not ev.is_set():
                return      # a push for this (shuffle, src) is in flight
            ev = self._repl_done[key] = threading.Event()

        def _push():
            try:
                self.replicate_shuffle(shuffle_id, k, src=src)
            finally:
                ev.set()
        # node-level durability work: the replica push deliberately
        # OUTLIVES the submitting task and its ambients — a cancelled or
        # completed map task's committed blocks must still replicate
        # (wait_replicated joins by event, not by task scope)
        # tpu-lint: allow-ambient-propagation(replication outlives the submitting task by design; inheriting its CancelToken would kill a committed push mid-flight)
        threading.Thread(target=_push, daemon=True).start()

    def wait_replicated(self, shuffle_id: int,
                        timeout_s: float = 30.0) -> bool:
        """Join every in-flight replica push for ``shuffle_id`` (all
        sources this node writes for)."""
        with self._repl_lock:
            evs = [ev for (sid, _), ev in self._repl_done.items()
                   if sid == int(shuffle_id)]
        deadline = time.monotonic() + max(float(timeout_s), 0.0)
        return all(ev.wait(max(deadline - time.monotonic(), 0.0))
                   for ev in evs)

    def replica_announce(self, shuffle_id: int, src: str,
                         holder: str) -> None:
        if self._driver is not None:
            PeerClient(self._driver).replica_announce(shuffle_id, src,
                                                      holder)
        elif self.registry is not None:
            self.registry.replica_announce(shuffle_id, src, holder)

    def replica_holders(self, shuffle_id: int, src: str) -> List[str]:
        try:
            if self._driver is not None:
                return PeerClient(self._driver).replica_holders(
                    shuffle_id, src)
            if self.registry is not None:
                return self.registry.replica_holders(shuffle_id, src)
        except OSError:
            pass
        # registry unreachable (or none): fall back to the catalog
        # snapshot pulled at registration
        for sid, csrc, holders in self._catalog.get("replicas", []):
            if int(sid) == int(shuffle_id) and csrc == src:
                return list(holders)
        return []

    def replica_client_for(self, shuffle_id: int,
                           src: str) -> Optional["ReplicaClient"]:
        """A duck-typed peer serving ``src``'s map output for this
        shuffle from its replica holders — None when no reachable holder
        is cataloged (the caller then escalates to scoped recovery)."""
        holders = [(eid, self._peers[eid])
                   for eid in self.replica_holders(shuffle_id, src)
                   if eid in self._peers and eid != src]
        # this node may itself hold a replica (common at small worlds):
        # serving it through its own server keeps one code path
        return ReplicaClient(src, holders) if holders else None

    def sync_catalog(self) -> None:
        """Pull the registry's shuffle/replica catalog (joiner warm-up:
        a rank that registers mid-session learns where every committed
        shuffle's copies live before its first task)."""
        if self._driver is None:
            return
        try:
            self._catalog = PeerClient(self._driver).catalog()
            SHUFFLE_COUNTERS.add(catalog_syncs=1)
        except OSError:
            self._catalog = {}

    def leave(self, drain: bool = True,
              timeout_s: Optional[float] = None) -> int:
        """Graceful departure: wait for in-flight replication pushes,
        re-replicate every primary shuffle this node still holds (so its
        map output survives it), then deregister.  Returns blocks
        drained.  In-flight queries keep completing through the replica
        catalog — the scoped-recovery path is never touched.

        The drain bound defaults to ``spark.rapids.cluster.drain.timeout``
        and the copy count to the configured replication factor (at least
        2 — a drain with replication off must still leave one surviving
        copy behind)."""
        # lazy: transport imports this module at load time
        from spark_rapids_tpu.shuffle.transport import replication_config
        factor, _persist, drain_timeout = replication_config()
        if timeout_s is None:
            timeout_s = drain_timeout
        deadline = time.monotonic() + max(float(timeout_s), 0.0)
        drained = 0
        if drain:
            with self._repl_lock:
                pending = list(self._repl_done.values())
            for ev in pending:
                ev.wait(max(deadline - time.monotonic(), 0.0))
            for sid in self.store.shuffle_ids():
                if time.monotonic() >= deadline:
                    break       # leave anyway; scoped recovery covers
                # each committed slot drains under its OWN src (readers
                # resolve replicas by slot); uncommitted standalone
                # blocks go under this node's id
                srcs = sorted(self.store.commits(sid)) \
                    or [self.executor_id]
                for s in srcs:
                    drained += self.replicate_shuffle(
                        sid, k=max(factor, 2), src=s, drain=True)
        try:
            if self._driver is not None:
                PeerClient(self._driver).leave(self.executor_id)
            elif self.registry is not None:
                self.registry.leave(self.executor_id)
        except OSError:
            pass        # registry gone too; nothing left to tell
        return drained

    def close(self) -> None:
        self.server.close()
