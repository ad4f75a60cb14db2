"""Multi-process control plane: shared-memory event ring + worker procs.

One **store-owner process** keeps the authoritative `ObjectStore` — single
writer, single resourceVersion sequence, WAL as the shared-storage analog
(the etcd position in the reference architecture). `KTPU_WORKER_PROCS`
**worker processes** each run a full serving loop + `KTPU_FANOUT_SHARDS`
delivery threads over a read-only mirror of the store. Two channels cross
the process boundary:

* **Event ring** (`multiprocessing.shared_memory`): the owner appends each
  event's encode-once `_Frame` wire bytes exactly once; every worker mmaps
  the same segment and fans frames out to its watchers with **zero
  per-process re-encode** (the worker's `watchcache_frames_encoded_total`
  stays 0 — the owner's counter is the encode ledger). The ring header
  carries `(min_rv, max_rv)`, so a reader the writer has lapped gets an
  honest 410 → snapshot resync → subscriber relist, never a silent gap.

* **Mutation RPC** (unix-domain socket, newline-delimited JSON): workers
  forward create/update/delete/patch/bind to the owner, which executes
  them against the real store — validation, admission, WAL, exactly-once
  all live there, so a replayed create answers AlreadyExists and a
  replayed bind answers Conflict exactly as today. The owner appends the
  ring record *before* writing the RPC response, so a worker that drains
  the ring to the response's rv (`RingPump.catch_up`) serves
  read-your-writes immediately.

`KTPU_WORKER_PROCS=0` (the default) pins the in-process topology —
byte-parity fallback, and what tier-1 runs.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import signal
import socket
import struct
import tempfile
import threading
import time
from dataclasses import dataclass
from multiprocessing import shared_memory
from typing import Any, Callable

from kubernetes_tpu.api import objects as objs
from kubernetes_tpu.apiserver.store import (
    AlreadyExists,
    Conflict,
    Expired,
    NotFound,
    ObjectStore,
    TooManyRequests,
    WatchEvent,
)

log = logging.getLogger("ktpu.multiproc")


def default_worker_procs() -> int:
    """`KTPU_WORKER_PROCS`: how many apiserver worker processes to run.
    0 (the default) pins today's in-process topology — the tier-1 parity
    fallback."""
    try:
        return max(0, int(os.environ.get("KTPU_WORKER_PROCS", "0")))
    except ValueError:
        return 0


def pin_to_core(worker_id: int) -> int | None:
    """Pin the calling process to one CPU (workers round-robin the
    affinity set). Best-effort: platforms without sched_setaffinity and
    restricted containers simply decline the pin."""
    if not hasattr(os, "sched_setaffinity"):
        return None
    try:
        cpus = sorted(os.sched_getaffinity(0))
        cpu = cpus[worker_id % len(cpus)]
        os.sched_setaffinity(0, {cpu})
        return cpu
    except OSError:
        log.warning("worker %d: sched_setaffinity refused", worker_id)
        return None


def _attach_shm(name: str) -> shared_memory.SharedMemory:
    """Attach to an existing segment. Pre-3.13 SharedMemory has no
    track=False, so attaching registers the segment with the resource
    tracker (bpo-39959) — but in this topology every attacher is a spawn
    CHILD of the creating owner, and spawn children inherit the parent's
    tracker fd, so the attach registration lands in the same dedup'd set
    as the owner's and the owner's unlink/unregister leaves the tracker
    clean. Never attach from a process that is not a descendant of the
    owner: its independent tracker would unlink the segment on exit,
    destroying the ring under everyone else."""
    return shared_memory.SharedMemory(name=name)


# ---- the ring ----
#
# layout (little-endian):
#   [0:64)                     header
#     u32 magic, u32 version
#     u64 head     — byte offset of the oldest retained record
#     u64 tail     — byte offset one past the newest record
#     u64 min_rv   — rv of the record at head (410 floor)
#     u64 max_rv   — rv of the record before tail
#     u64 capacity — data region size in bytes
#     u64 n_slots  — reader slot count
#   [64 : 64+32*n_slots)       reader slots, 32 bytes each:
#     u64 pid, u64 read_pos, u64 last_rv, u64 reserved
#   [data_off : data_off+capacity)  record bytes
#
# head/tail/read_pos are MONOTONIC byte offsets; the physical index is
# offset % capacity, so a record may wrap the physical end in two parts.
# Records are `[u32 len][u64 rv][payload]`. Single writer (the owner);
# readers synchronize with a seqlock: re-check head after copying — if
# head moved past the copy's start, the bytes may be torn → Expired.

_MAGIC = 0x4B545055  # "KTPU"
_VERSION = 1
_HDR = struct.Struct("<II")
_HDR_SIZE = 64
_HEAD_OFF = 8
_TAIL_OFF = 16
_MINRV_OFF = 24
_MAXRV_OFF = 32
_CAP_OFF = 40
_NSLOTS_OFF = 48
_SLOTS_OFF = 64
_SLOT = struct.Struct("<QQQQ")
_REC = struct.Struct("<IQ")  # length, resource_version
_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")


class EventRing:
    """Single-writer multi-reader byte ring over one SharedMemory segment.

    Writer order matters: head (and min_rv) advance BEFORE the reclaimed
    bytes are overwritten, the record's bytes land before tail moves, and
    tail moves last — so a reader either sees a fully-written record or,
    if the writer lapped it mid-copy, detects the lap from head and raises
    Expired (the honest-410 signal). All header fields are single u64
    stores, atomic under the GIL / a single mmap word."""

    def __init__(self, shm: shared_memory.SharedMemory, *, owner: bool):
        self._shm = shm
        self._buf = shm.buf
        self._owner = owner
        self._closed = False
        magic, version = _HDR.unpack_from(self._buf, 0)
        if owner is False and (magic != _MAGIC or version != _VERSION):
            raise ValueError(
                f"shared segment {shm.name!r} is not a ktpu event ring "
                f"(magic {magic:#x} version {version})")
        self.capacity = self._get_u64(_CAP_OFF)
        self.n_slots = self._get_u64(_NSLOTS_OFF)
        self._data_off = _SLOTS_OFF + _SLOT.size * self.n_slots
        # owner-side O(events) proof: exactly one append per published
        # event, independent of worker/watcher count
        self.appends = 0

    # -- construction --

    @classmethod
    def create(cls, *, name: str | None = None,
               capacity: int = 1 << 22, n_slots: int = 16) -> "EventRing":
        size = _HDR_SIZE + _SLOT.size * n_slots + capacity
        shm = shared_memory.SharedMemory(name=name, create=True, size=size)
        buf = shm.buf
        buf[:size] = b"\x00" * size
        _HDR.pack_into(buf, 0, _MAGIC, _VERSION)
        _U64.pack_into(buf, _CAP_OFF, capacity)
        _U64.pack_into(buf, _NSLOTS_OFF, n_slots)
        return cls(shm, owner=True)

    @classmethod
    def attach(cls, name: str) -> "EventRing":
        return cls(_attach_shm(name), owner=False)

    @property
    def name(self) -> str:
        return self._shm.name

    # -- header accessors --

    def _get_u64(self, off: int) -> int:
        return _U64.unpack_from(self._buf, off)[0]

    def _set_u64(self, off: int, value: int) -> None:
        _U64.pack_into(self._buf, off, value)

    @property
    def head(self) -> int:
        return self._get_u64(_HEAD_OFF)

    @property
    def tail(self) -> int:
        return self._get_u64(_TAIL_OFF)

    @property
    def min_rv(self) -> int:
        return self._get_u64(_MINRV_OFF)

    @property
    def max_rv(self) -> int:
        return self._get_u64(_MAXRV_OFF)

    # -- reader slots --

    def slot(self, i: int) -> tuple[int, int, int]:
        """(pid, read_pos, last_rv) for reader slot i."""
        pid, pos, last_rv, _ = _SLOT.unpack_from(
            self._buf, _SLOTS_OFF + _SLOT.size * i)
        return pid, pos, last_rv

    def set_slot(self, i: int, *, pid: int | None = None,
                 read_pos: int | None = None,
                 last_rv: int | None = None) -> None:
        base = _SLOTS_OFF + _SLOT.size * i
        if pid is not None:
            _U64.pack_into(self._buf, base, pid)
        if read_pos is not None:
            _U64.pack_into(self._buf, base + 8, read_pos)
        if last_rv is not None:
            _U64.pack_into(self._buf, base + 16, last_rv)

    def claim_slot(self, i: int, pid: int) -> None:
        if not 0 <= i < self.n_slots:
            raise ValueError(f"worker id {i} out of range "
                             f"(ring has {self.n_slots} slots)")
        self.set_slot(i, pid=pid)

    def release_slot(self, i: int) -> tuple[int, int]:
        """Clear a dead reader's pid but KEEP read_pos/last_rv — the
        respawned worker's resume bookkeeping. Returns (read_pos,
        last_rv) as observed."""
        _, pos, last_rv = self.slot(i)
        self.set_slot(i, pid=0)
        return pos, last_rv

    # -- modular byte copies --

    def _write_at(self, pos: int, data: bytes) -> None:
        off = self._data_off + pos % self.capacity
        limit = self._data_off + self.capacity
        n = len(data)
        if off + n <= limit:
            self._buf[off:off + n] = data
        else:
            first = limit - off
            self._buf[off:limit] = data[:first]
            self._buf[self._data_off:self._data_off + n - first] = \
                data[first:]

    def _read_at(self, pos: int, n: int) -> bytes:
        off = self._data_off + pos % self.capacity
        limit = self._data_off + self.capacity
        if off + n <= limit:
            return bytes(self._buf[off:off + n])
        first = limit - off
        return bytes(self._buf[off:limit]) + \
            bytes(self._buf[self._data_off:self._data_off + n - first])

    # -- writer (owner only) --

    def append(self, rv: int, payload: bytes) -> None:
        rec = _REC.pack(len(payload), rv) + payload
        need = len(rec)
        if need > self.capacity:
            raise ValueError(
                f"event of {need} bytes exceeds ring capacity "
                f"{self.capacity}")
        head = self.head
        tail = self.tail
        # reclaim whole records until the new one fits; head (and min_rv)
        # move before any reclaimed byte is overwritten, so a lapped
        # reader's seqlock re-check always fires
        while tail + need - head > self.capacity:
            head = self._advance_head(head, tail)
        self._write_at(tail, rec)
        if head == tail:  # ring was empty: this record is now the oldest
            self._set_u64(_MINRV_OFF, rv)
        self._set_u64(_MAXRV_OFF, rv)
        self._set_u64(_TAIL_OFF, tail + need)
        self.appends += 1

    def _advance_head(self, head: int, tail: int) -> int:
        plen = _U32.unpack(self._read_at(head, 4))[0]
        new_head = head + _REC.size + plen
        self._set_u64(_HEAD_OFF, new_head)
        if new_head < tail:
            next_rv = _U64.unpack(self._read_at(new_head + 4, 8))[0]
            self._set_u64(_MINRV_OFF, next_rv)
        return new_head

    # -- reader --

    def read(self, pos: int,
             max_records: int = 1024) -> tuple[int, list[tuple[int, bytes]]]:
        """Read records from monotonic offset `pos`. Returns (new_pos,
        [(rv, payload), ...]); empty list when caught up. Raises Expired
        when the writer has lapped this reader — the caller must resync
        from a snapshot (honest 410, never a silent gap)."""
        tail = self.tail
        if pos >= tail:
            return pos, []
        if pos < self.head:
            raise Expired(
                f"ring overrun: reader at {pos}, window starts at "
                f"{self.head} (min rv {self.min_rv})")
        out: list[tuple[int, bytes]] = []
        while pos < tail and len(out) < max_records:
            plen, rv = _REC.unpack(self._read_at(pos, _REC.size))
            if pos + _REC.size + plen > tail:
                # a valid record never extends past the tail we snapped:
                # the header bytes were torn by a lapping writer
                raise Expired("ring overrun: torn record header")
            payload = self._read_at(pos + _REC.size, plen)
            if pos < self.head:  # seqlock: copy may be torn — discard
                raise Expired(
                    f"ring overrun during read (window starts at "
                    f"{self.head}, min rv {self.min_rv})")
            out.append((rv, payload))
            pos += _REC.size + plen
        return pos, out

    # -- lifetime --

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._buf = None
        self._shm.close()

    def unlink(self) -> None:
        try:
            self._shm.unlink()
        except FileNotFoundError:
            pass


# ---- RPC plumbing ----

def _rpc_exception(name: str, message: str) -> Exception:
    """Rehydrate an owner-side exception by class name (the store's public
    error vocabulary plus validation/admission)."""
    from kubernetes_tpu.apiserver.admission import AdmissionError
    from kubernetes_tpu.apiserver.validation import ValidationError

    table: dict[str, type[Exception]] = {
        "NotFound": NotFound,
        "AlreadyExists": AlreadyExists,
        "Conflict": Conflict,
        "Expired": Expired,
        "TooManyRequests": TooManyRequests,
        "ValidationError": ValidationError,
        "AdmissionError": AdmissionError,
        "PermissionError": PermissionError,
    }
    return table.get(name, ValueError)(message)


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


class StoreOwner:
    """Owner-process runtime around the authoritative ObjectStore: the
    ring writer (an event tap — one append per published event, after
    WAL + history, in rv order) and the unix-socket RPC server the
    workers forward mutations to. Lives on the owner's event loop."""

    def __init__(self, store: ObjectStore, *,
                 rpc_path: str | None = None,
                 ring_name: str | None = None,
                 ring_capacity: int = 1 << 22,
                 n_slots: int = 16):
        self.store = store
        self.ring = EventRing.create(name=ring_name,
                                     capacity=ring_capacity,
                                     n_slots=n_slots)
        if rpc_path is None:
            rpc_path = os.path.join(
                tempfile.mkdtemp(prefix="ktpu-mp-"), "owner.sock")
        self.rpc_path = rpc_path
        self._server: asyncio.AbstractServer | None = None
        # the encode-once ledger: wire bytes produced exactly here, once
        # per event, shared by every worker process via the ring
        self.frames_encoded = 0
        self.rpc_requests = 0
        store.event_taps.append(self._ring_tap)

    def _ring_tap(self, event: WatchEvent) -> None:
        from kubernetes_tpu.apiserver.watchcache import _Frame

        payload = _Frame(event).json_bytes()
        self.frames_encoded += 1
        self.ring.append(event.resource_version, payload)

    # -- lifecycle --

    async def start(self) -> "StoreOwner":
        self._server = await asyncio.start_unix_server(
            self._serve_conn, path=self.rpc_path)
        return self

    async def aclose(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        try:
            self.store.event_taps.remove(self._ring_tap)
        except ValueError:
            pass
        try:
            os.unlink(self.rpc_path)
        except OSError:
            pass
        self.ring.close()
        self.ring.unlink()

    # -- worker liveness --

    def dead_workers(self) -> list[int]:
        """Reader slots whose registered pid no longer exists."""
        out = []
        for i in range(self.ring.n_slots):
            pid, _, _ = self.ring.slot(i)
            if pid and not _pid_alive(pid):
                out.append(i)
        return out

    def reclaim_slot(self, worker_id: int) -> tuple[int, int]:
        """Crash cleanup: clear the dead worker's pid, keep its
        read_pos/last_rv so the respawn resumes without replaying frames
        the dead process already delivered."""
        return self.ring.release_slot(worker_id)

    # -- RPC server --

    async def _serve_conn(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                line = await reader.readline()
                if not line:
                    return
                try:
                    req = json.loads(line)
                except ValueError:
                    return
                resp = self._dispatch(req)
                writer.write(json.dumps(resp).encode() + b"\n")
                await writer.drain()
        except (ConnectionError, asyncio.CancelledError):
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    def _dispatch(self, req: dict) -> dict:
        self.rpc_requests += 1
        rid = req.get("id")
        verb = req.get("verb", "")
        handler: Callable[[dict], Any] | None = getattr(
            self, f"_rpc_{verb}", None)
        if handler is None:
            return {"id": rid, "ok": False, "error": "ValueError",
                    "message": f"unknown verb {verb!r}"}
        try:
            return {"id": rid, "ok": True, "result": handler(req)}
        except Exception as e:
            # every store/validation/admission error crosses by class
            # name; the worker rehydrates it — this is how a replayed
            # create answers AlreadyExists and a replayed bind Conflict
            return {"id": rid, "ok": False, "error": type(e).__name__,
                    "message": str(e)}

    # -- verbs --

    def _rpc_ping(self, req: dict) -> dict:
        return {"rv": self.store.resource_version}

    def _rpc_register(self, req: dict) -> dict:
        wid = int(req["worker_id"])
        self.ring.claim_slot(wid, int(req["pid"]))
        return {"slot": wid, "ring": self.ring.name}

    def _rpc_snapshot(self, req: dict) -> dict:
        from kubernetes_tpu.apiserver.http import encode_object

        store = self.store
        objects = [[kind, encode_object(obj)]
                   for kind, bucket in store._objects.items()
                   for obj in bucket.values()]
        history = [[e.type, e.kind, e.resource_version,
                    encode_object(e.obj)] for e in store._history]
        # ring_pos is exact, not racy: the owner loop is single-threaded
        # and the tap appends synchronously inside every mutation, so
        # tail here covers precisely the events up to resource_version
        return {"rv": store.resource_version, "ring_pos": self.ring.tail,
                "min_rv": self.ring.min_rv,
                "objects": objects, "history": history}

    def _rpc_create(self, req: dict) -> dict:
        from kubernetes_tpu.apiserver.http import (decode_object,
                                                   encode_object)

        out = self.store.create(decode_object(req["kind"], req["obj"]))
        return {"rv": self.store.resource_version,
                "obj": encode_object(out)}

    def _rpc_create_many(self, req: dict) -> dict:
        from kubernetes_tpu.apiserver.http import (decode_object,
                                                   encode_object)

        out = self.store.create_many(
            [decode_object(k, o) for k, o in req["objs"]])
        return {"rv": self.store.resource_version,
                "objs": [encode_object(o) for o in out]}

    def _rpc_update(self, req: dict) -> dict:
        from kubernetes_tpu.apiserver.http import (decode_object,
                                                   encode_object)

        out = self.store.update(decode_object(req["kind"], req["obj"]),
                                check_version=req.get("check_version",
                                                      True))
        return {"rv": self.store.resource_version,
                "obj": encode_object(out)}

    def _rpc_delete(self, req: dict) -> dict:
        from kubernetes_tpu.apiserver.http import encode_object

        out = self.store.delete(req["kind"], req["name"],
                                req.get("ns", "default"))
        return {"rv": self.store.resource_version,
                "obj": encode_object(out)}

    def _rpc_patch(self, req: dict) -> dict:
        from kubernetes_tpu.apiserver.http import encode_object

        out = self.store.patch(
            req["kind"], req["name"], req.get("ns", "default"),
            req["patch"],
            req.get("content_type", "application/merge-patch+json"))
        return {"rv": self.store.resource_version,
                "obj": encode_object(out)}

    def _rpc_bind(self, req: dict) -> dict:
        from kubernetes_tpu.apiserver.http import encode_object

        out = self.store.bind(objs.Binding(
            pod_name=req["pod"], namespace=req["ns"],
            target_node=req["node"]))
        return {"rv": self.store.resource_version,
                "obj": encode_object(out)}

    def _rpc_bind_many(self, req: dict) -> dict:
        from kubernetes_tpu.apiserver.http import encode_object

        bindings = [objs.Binding(pod_name=p, namespace=ns, target_node=n)
                    for ns, p, n in req["bindings"]]
        bound, errors = self.store.bind_many(bindings)
        return {
            "rv": self.store.resource_version,
            "bound": [encode_object(o) if o is not None else None
                      for o in bound],
            "errors": [[type(e).__name__, str(e)] if e is not None
                       else None for e in errors],
        }


class RpcClient:
    """Blocking newline-JSON RPC over the owner's unix socket, called
    from the worker's synchronous store verbs (the serving path runs
    store calls synchronously today, so one blocking round-trip here is
    the same latency discipline as the in-process call it replaces).
    Thread-safe; one in-flight request at a time."""

    def __init__(self, path: str, timeout_s: float = 30.0):
        self._path = path
        self._timeout_s = timeout_s
        self._sock: socket.socket | None = None
        self._rfile = None
        self._lock = threading.Lock()
        self._seq = 0

    def _ensure(self) -> None:
        if self._sock is None:
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.settimeout(self._timeout_s)
            sock.connect(self._path)
            self._sock = sock
            self._rfile = sock.makefile("rb")

    def _reset(self) -> None:
        if self._rfile is not None:
            try:
                self._rfile.close()
            except OSError:
                pass
            self._rfile = None
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def close(self) -> None:
        with self._lock:
            self._reset()

    def call(self, verb: str, **params) -> Any:
        with self._lock:
            self._seq += 1
            data = json.dumps(
                {"id": self._seq, "verb": verb, **params}).encode() + b"\n"
            line = b""
            for attempt in (0, 1):
                try:
                    self._ensure()
                    self._sock.sendall(data)
                    line = self._rfile.readline()
                    if not line:
                        raise ConnectionError("owner closed the RPC socket")
                    break
                except (ConnectionError, OSError):
                    # one reconnect. A torn socket is ambiguous — the verb
                    # may have executed before the tear — but exactly-once
                    # is the STORE's guarantee, not the transport's: the
                    # replay answers AlreadyExists/Conflict, the same
                    # contract RemoteStore documents for failover retries
                    self._reset()
                    if attempt:
                        raise
        resp = json.loads(line)
        if resp.get("ok"):
            return resp.get("result")
        raise _rpc_exception(resp.get("error", ""),
                             resp.get("message", ""))


# ---- worker side ----

def _load_mirror_snapshot(mirror: ObjectStore, snap: dict) -> None:
    """Replace the mirror's state wholesale with an owner snapshot."""
    from kubernetes_tpu.apiserver.http import decode_object

    buckets: dict[str, dict] = {}
    for kind, body in snap["objects"]:
        obj = decode_object(kind, body)
        key = (obj.metadata.namespace or "default", obj.metadata.name)
        buckets.setdefault(kind, {})[key] = obj
        if kind == "Service":
            mirror._reserve_cluster_ip(obj.spec.get("clusterIP", ""))
    mirror._objects = buckets
    mirror._rv = int(snap["rv"])
    mirror._history.clear()
    for etype, kind, rv, body in snap.get("history", []):
        mirror._history.append(
            WatchEvent(etype, kind, decode_object(kind, body), int(rv)))


class RingPump:
    """Worker-side ring consumer. Drains the shared-memory ring on the
    serving loop, applying each record to the mirror store and pushing
    the owner-encoded bytes into the external-feed watch cache. Also
    called synchronously after every forwarded write (`catch_up`) so the
    worker serves read-your-writes. On overrun — the writer lapped this
    reader — takes the honest-410 path: full resync from an owner
    snapshot, every cache subscriber evicted to relist."""

    def __init__(self, ring: EventRing, slot: int, mirror: ObjectStore,
                 cache, rpc: RpcClient, poll_s: float = 0.001):
        self.ring = ring
        self.slot = slot
        self.mirror = mirror
        self.cache = cache
        self.rpc = rpc
        self._poll_s = poll_s
        self._pos = 0
        self.last_rv = 0
        self.applied = 0
        self.resyncs = 0
        self._stopping = False

    def seed(self, ring_pos: int, rv: int) -> None:
        """Set the resume point from an owner snapshot. `last_rv` only
        ratchets up: a respawned worker that inherited a higher last_rv
        from the dead process's slot keeps it, so frames the dead worker
        already delivered are never replayed to clients."""
        self._pos = ring_pos
        self.last_rv = max(self.last_rv, rv)
        self.ring.set_slot(self.slot, read_pos=self._pos,
                           last_rv=self.last_rv)

    def drain(self) -> int:
        """One synchronous drain pass; returns records applied."""
        try:
            pos, records = self.ring.read(self._pos)
        except Expired:
            self.resync()
            return 0
        for rv, payload in records:
            self._apply(rv, payload)
        if records:
            self._pos = pos
            self.ring.set_slot(self.slot, read_pos=self._pos,
                               last_rv=self.last_rv)
        return len(records)

    def catch_up(self, target_rv: int, timeout_s: float = 5.0) -> None:
        """Drain until the mirror covers `target_rv`. The owner appends
        the ring record before answering the RPC, so the bytes are
        already in shared memory — the loop normally completes on the
        first pass without waiting."""
        deadline = time.monotonic() + timeout_s
        while self.last_rv < target_rv:
            if self.drain() == 0:
                if time.monotonic() >= deadline:
                    log.warning("ring catch-up to rv %d stalled at rv %d",
                                target_rv, self.last_rv)
                    return
                # thread-only path: catch_up runs on the RPC caller's
                # thread, never an event loop
                time.sleep(0)  # ktpu: allow[blocking-in-async]

    def _apply(self, rv: int, payload: bytes) -> None:
        if rv <= self.last_rv:
            return  # snapshot overlap / already-delivered (respawn) guard
        from kubernetes_tpu.apiserver.http import decode_object

        d = json.loads(payload)
        body = d["object"]
        obj = decode_object(body.get("kind", ""), body)
        event = WatchEvent(d["type"], obj.kind, obj, rv)
        self.mirror.apply_external_event(event)
        if self.cache is not None:
            self.cache.ingest_external(event, payload)
        self.last_rv = rv
        self.applied += 1

    def resync(self) -> None:
        snap = self.rpc.call("snapshot")
        _load_mirror_snapshot(self.mirror, snap)
        self._pos = int(snap["ring_pos"])
        self.last_rv = int(snap["rv"])
        self.resyncs += 1
        self.ring.set_slot(self.slot, read_pos=self._pos,
                           last_rv=self.last_rv)
        if self.cache is not None:
            self.cache.rebuild_external()

    async def run(self) -> None:
        """Poll task on the serving loop: back-to-back while busy, naps
        while idle."""
        while not self._stopping:
            if self.drain():
                await asyncio.sleep(0)
            else:
                await asyncio.sleep(self._poll_s)

    def stop(self) -> None:
        self._stopping = True


class WorkerStore:
    """Store facade inside a worker process: reads, watches, and the
    serving surface (`_history`, `resource_version`, ...) come from the
    ring-fed mirror via attribute delegation; mutating verbs forward to
    the owner over RPC, then drain the ring to the response's rv so this
    worker immediately reads its own write."""

    def __init__(self, mirror: ObjectStore, rpc: RpcClient,
                 pump: RingPump):
        self.mirror = mirror
        self._rpc = rpc
        self._pump = pump

    def __getattr__(self, name: str) -> Any:
        return getattr(self.mirror, name)

    def _sync(self, res: dict) -> dict:
        self._pump.catch_up(int(res.get("rv", 0)))
        return res

    # -- forwarded verbs --

    def create(self, obj: Any, **_kw) -> Any:
        from kubernetes_tpu.apiserver.http import (decode_object,
                                                   encode_object)

        res = self._sync(self._rpc.call(
            "create", kind=obj.kind, obj=encode_object(obj)))
        return decode_object(obj.kind, res["obj"])

    def create_many(self, objects: list) -> list:
        from kubernetes_tpu.apiserver.http import (decode_object,
                                                   encode_object)

        res = self._sync(self._rpc.call(
            "create_many",
            objs=[[o.kind, encode_object(o)] for o in objects]))
        return [decode_object(d.get("kind", ""), d) for d in res["objs"]]

    def update(self, obj: Any, *, check_version: bool = True) -> Any:
        from kubernetes_tpu.apiserver.http import (decode_object,
                                                   encode_object)

        res = self._sync(self._rpc.call(
            "update", kind=obj.kind, obj=encode_object(obj),
            check_version=check_version))
        return decode_object(obj.kind, res["obj"])

    def delete(self, kind: str, name: str,
               namespace: str = "default") -> Any:
        from kubernetes_tpu.apiserver.http import decode_object

        res = self._sync(self._rpc.call(
            "delete", kind=kind, name=name, ns=namespace))
        return decode_object(kind, res["obj"])

    def patch(self, kind: str, name: str, namespace: str, patch: Any,
              content_type: str = "application/merge-patch+json",
              **_kw) -> Any:
        from kubernetes_tpu.apiserver.http import decode_object

        res = self._sync(self._rpc.call(
            "patch", kind=kind, name=name, ns=namespace, patch=patch,
            content_type=content_type))
        return decode_object(kind, res["obj"])

    def guaranteed_update(self, kind: str, name: str, namespace: str,
                          mutate: Callable[[Any], Any],
                          retries: int = 16) -> Any:
        # the mutate callable can't cross the process boundary: run the
        # CAS loop here against the mirror, retrying on Conflict after
        # draining the ring to the owner's current rv
        last: Exception = Conflict(
            f"{kind} {namespace}/{name}: too many CAS retries")
        for _ in range(max(1, retries)):
            try:
                obj = self.mirror.get(kind, name, namespace)
            except NotFound:
                # mirror may trail a sibling worker's create: catch up
                # to the owner clock once, then let NotFound propagate
                self._pump.catch_up(int(self._rpc.call("ping")["rv"]))
                obj = self.mirror.get(kind, name, namespace)
            replacement = mutate(obj)
            if replacement is not None:
                obj = replacement
            try:
                return self.update(obj)
            except Conflict as e:
                last = e
                self._pump.catch_up(int(self._rpc.call("ping")["rv"]))
        raise last

    def bind(self, binding: Any) -> Any:
        from kubernetes_tpu.apiserver.http import decode_object

        res = self._sync(self._rpc.call(
            "bind", ns=binding.namespace, pod=binding.pod_name,
            node=binding.target_node))
        return decode_object("Pod", res["obj"])

    def bind_many(self, bindings: list) -> tuple[list, list]:
        from kubernetes_tpu.apiserver.http import decode_object

        res = self._sync(self._rpc.call(
            "bind_many",
            bindings=[[b.namespace, b.pod_name, b.target_node]
                      for b in bindings]))
        bound = [decode_object("Pod", d) if d is not None else None
                 for d in res["bound"]]
        errors = [_rpc_exception(e[0], e[1]) if e is not None else None
                  for e in res["errors"]]
        return bound, errors


# ---- worker process entry point ----

@dataclass
class WorkerSpec:
    """Picklable bootstrap config for one worker process. The spawn
    target receives ONLY this — names and numbers, never live handles
    (sockets, loops, stores, shared memory): every handle is constructed
    inside the child (lint R7's discipline)."""

    worker_id: int
    ring_name: str
    rpc_path: str
    host: str = "127.0.0.1"
    port: int = 0  # pre-pick with free_port(): the parent needs it
    shards: int | None = None
    watch_window: int = 4096
    advertise: bool = True
    heartbeat_s: float | None = None
    bench_watchers: int = 0
    bench_kind: str = "Pod"
    poll_s: float = 0.001


def free_port(host: str = "127.0.0.1") -> int:
    """Pre-pick a port for a worker: the parent must know the endpoint
    before the child exists."""
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def wait_port(host: str, port: int, timeout_s: float = 15.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with socket.create_connection((host, port), timeout=0.25):
                return True
        except OSError:
            # wait_port is called via asyncio.to_thread / from sync
            # harness code only
            time.sleep(0.02)  # ktpu: allow[blocking-in-async]
    return False


def spawn_worker(spec: WorkerSpec):
    """Spawn one worker via the *spawn* context — a forked child would
    inherit the parent's live loop/socket/shm handles (lint R7)."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    proc = ctx.Process(target=worker_main, args=(spec,),
                       name=f"ktpu-worker-{spec.worker_id}", daemon=True)
    proc.start()
    return proc


def worker_main(spec: WorkerSpec) -> None:
    """Module-level spawn target of one apiserver worker process."""
    # a worker serves the API only: should anything it imports reach for
    # JAX, it gets the CPU — the accelerator belongs to the process that
    # opened it first (one process per chip)
    os.environ["JAX_PLATFORMS"] = "cpu"
    pin_to_core(spec.worker_id)
    try:
        asyncio.run(_worker_serve(spec))
    except KeyboardInterrupt:
        pass


def _attach_bench_sinks(cache, spec: WorkerSpec) -> None:
    """bench[multiproc]'s in-process watcher population: each sink
    touches the frame's wire bytes exactly as the HTTP write path does,
    so delivery counts and the encode-once ledger measure the real
    pipeline without 100k live sockets per worker."""
    if not spec.bench_watchers:
        return

    def sink(frame) -> None:
        frame.json_bytes()

    for _ in range(spec.bench_watchers):
        cache.watch_sink(spec.bench_kind, sink=sink)


async def _worker_serve(spec: WorkerSpec) -> None:
    from kubernetes_tpu.apiserver.http import APIServer
    from kubernetes_tpu.apiserver.watchcache import WatchCache
    from kubernetes_tpu.obs import metrics as obs_metrics

    ring = EventRing.attach(spec.ring_name)
    rpc = RpcClient(spec.rpc_path)
    rpc.call("register", worker_id=spec.worker_id, pid=os.getpid())
    # per-process /metrics identity: every scrape of this worker carries
    # its own `worker` label (each process renders its own registry)
    obs_metrics.REGISTRY.gauge(
        "ktpu_worker_up", "1 while this worker process serves.",
        labels=("worker",)).labels(str(spec.worker_id)).set(1)
    _, _, slot_last_rv = ring.slot(spec.worker_id)
    mirror = ObjectStore(watch_window=spec.watch_window)
    cache = WatchCache(mirror, shards=spec.shards)
    pump = RingPump(ring, spec.worker_id, mirror, cache, rpc,
                    poll_s=spec.poll_s)
    snap = rpc.call("snapshot")
    _load_mirror_snapshot(mirror, snap)
    # respawn resume: the slot's last_rv survives the crash; seed() keeps
    # the max of it and the snapshot rv, so nothing already delivered by
    # the dead process replays
    pump.last_rv = int(slot_last_rv)
    pump.seed(int(snap["ring_pos"]), int(snap["rv"]))
    cache.start_external()
    store = WorkerStore(mirror, rpc, pump)
    server = APIServer(store, host=spec.host, port=spec.port,
                       watch_cache=True,
                       replica_id=f"worker-{spec.worker_id}")
    server.watch_cache = cache  # pre-built, externally fed
    if spec.heartbeat_s is not None:
        server.watch_heartbeat_s = spec.heartbeat_s
    await server.start()
    pump_task = asyncio.get_running_loop().create_task(pump.run())
    if spec.advertise:
        server.advertise()
    _attach_bench_sinks(cache, spec)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, ValueError):
            pass
    await stop.wait()
    # graceful exit (SIGTERM): DRAIN every watcher, join shard threads,
    # detach from the ring — the segment's lifetime belongs to the owner
    pump.stop()
    pump_task.cancel()
    try:
        await pump_task
    except asyncio.CancelledError:
        pass
    if spec.advertise:
        try:
            server.unadvertise()
        except Exception:
            pass
    await server.drain(timeout=2.0)
    rpc.close()
    ring.close()
