"""Multi-process serving: SO_REUSEPORT workers around one device owner.

The single-process daemon tops out on the Python wire stack (proto +
HTTP + GIL) long before the engine does — round 3 measured ~74 RPS
through the daemon against ~19k checks/s on-device.  The reference
scales by running on multi-core Go; the Python analog is processes:

* **one device owner** holds the real `DeviceCheckEngine` (a JAX device
  belongs to one process) and serves batched check/expand over a unix
  domain socket (`EngineHostServer`);
* **N workers** each run the full gRPC/REST daemon on the SAME public
  ports via ``SO_REUSEPORT`` (the kernel load-balances accepted
  connections) with a `RemoteCheckEngine` that forwards batches to the
  owner.  The owner's coalescer merges concurrent single checks from
  ALL workers into shared device waves, so cross-process fan-in feeds
  bigger (faster) batches, not contention.

Workers and owner share one durable store DSN (sqlite file / postgres);
writes land in the store from any worker and reach the device through
the owner's ordinary change-log drain.  A ``memory`` DSN cannot be
shared across processes and is refused.

Wire protocol (server/wire.py): length-prefixed binary frames — a JSON
meta section plus packed numpy arrays, with an optional shared-memory
hop for large payloads.  A worker pre-encodes tuples it has seen before
as ``int32 (n, 4)`` id rows against a MIRROR of the owner's vocabulary
(learned from responses, invalidated by a vocab epoch counter when the
owner's engine swaps vocabularies on snapshot resume); unseen tuples
ride as canonical strings and come back with their id rows so the next
batch sends ids.  One owner round-trip per worker batch, whatever the
batch size.  Typed errors re-raise client-side by status code.  The
socket is a trusted same-host channel (mode 0700 directory
recommended); no pickle.
"""

from __future__ import annotations

import os
import random
import socket
import socketserver
import subprocess
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ketotpu import deadline, faults, flightrec
from ketotpu.cache import SingleFlight
from ketotpu.cache import check_key as cache_check_key
from ketotpu.cache import context as cache_context
from ketotpu.engine import columns as colmod
from ketotpu.server import wire
from ketotpu.api.types import (
    DeadlineExceededError,
    KetoAPIError,
    RelationTuple,
    Subject,
    SubjectID,
    SubjectSet,
    Tree,
)

#: a worker's vocab mirror is bounded; on overflow it simply resets and
#: relearns (the owner remains the source of truth either way)
_MIRROR_CAP = 262144


def _encode_subject(s: Subject) -> str:
    return s.unique_id()


def _decode_subject(u: str) -> Subject:
    if u.startswith("set:"):
        return SubjectSet.from_string(u[4:])
    return SubjectID(u[3:] if u.startswith("id:") else u)


class EngineHostServer:
    """The device owner's unix-socket engine service."""

    def __init__(self, registry, path: str,
                 health_fn: Optional[Callable[[], dict]] = None):
        self.registry = registry
        self.path = path
        self.health_fn = health_fn
        self._shm_threshold = int(
            registry.config.get("engine.wire_shm_threshold", 262144)
        )
        # vocab epoch: bumped whenever the device engine swaps vocabulary
        # objects (snapshot resume, store-vocab adoption) so worker id
        # mirrors learned against the old id space get invalidated
        self._vocab_obj = None
        self._vepoch = 0
        self._rev: Optional[dict] = None
        # live accepted connections: stop() severs them so an attached
        # standby observes the owner's death exactly as a kill -9 would
        # (shutdown() alone only stops the accept loop)
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        if os.path.exists(path):
            os.unlink(path)

        host = self

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                with host._conns_lock:
                    host._conns.add(self.connection)
                ring = wire.ShmRing()
                shm_cache = wire.ShmCache()
                try:
                    while True:
                        try:
                            got = wire.recv_frame(
                                self.rfile, shm_cache=shm_cache
                            )
                        except wire.WireError:
                            break  # desynced peer: drop the connection
                        if got is None:
                            break
                        meta, arrays, nread = got
                        host._wire_count("rx", nread)
                        if faults.should("worker_error"):
                            # chaos: the owner wedges mid-exchange — the
                            # request dies with NO response frame, so the
                            # worker sees a transport failure (the lane
                            # fault the worker-wire breaker trips on, as
                            # opposed to owner_handler's typed error
                            # frame riding back on a healthy wire)
                            break
                        try:
                            faults.inject("owner_handler")
                            resp, resp_arrays = host._serve_frame(
                                meta, arrays
                            )
                        except Exception as e:  # noqa: BLE001
                            resp, resp_arrays = {"error": {
                                "msg": str(e),
                                "status": getattr(e, "status_code", 500),
                            }}, None
                        try:
                            sent = wire.send_frame(
                                self.connection, resp, resp_arrays,
                                ring=ring,
                                shm_threshold=host._shm_threshold,
                            )
                        except OSError:
                            break
                        host._wire_count("tx", sent)
                finally:
                    with host._conns_lock:
                        host._conns.discard(self.connection)
                    ring.close()
                    shm_cache.close()

        class Srv(socketserver.ThreadingUnixStreamServer):
            daemon_threads = True
            allow_reuse_address = True

        self._srv = Srv(path, Handler)
        self._thread = threading.Thread(
            target=self._srv.serve_forever, daemon=True,
            name="engine-host",
        )

    def start(self) -> "EngineHostServer":
        self._thread.start()
        return self

    def is_alive(self) -> bool:
        return self._thread.is_alive()

    def restart(self) -> "EngineHostServer":
        """Replace a dead host with a fresh one on the same socket path.

        The supervisor calls this when the serving thread died; pooled
        worker connections to the old socket fail and reconnect through
        their backoff path."""
        try:
            self._srv.server_close()
        except OSError:
            pass
        fresh = EngineHostServer(self.registry, self.path, self.health_fn)
        return fresh.start()

    def _wire_count(self, direction: str, nbytes: int) -> None:
        self.registry.metrics().counter(
            "keto_wire_bytes_total", float(nbytes),
            help="engine-wire socket bytes by direction", dir=direction,
        )

    def _vocab_state(self):
        """(vocab, epoch) of the owner's device engine, tracking object
        identity: a swapped vocab (checkpoint resume) bumps the epoch."""
        try:
            eng = self.registry._device_engine()
        except Exception:  # noqa: BLE001 - oracle/remote registries
            eng = None
        vocab = getattr(eng, "_vocab", None)
        if vocab is None:
            return None, 0
        if vocab is not self._vocab_obj:
            self._vocab_obj = vocab
            self._vepoch += 1
            # id -> string: ``Interner.string`` extends its own view as
            # the interner grows (``strings()`` would copy the whole table)
            self._rev = {
                "ns": vocab.namespaces.string,
                "obj": vocab.objects.string,
                "rel": vocab.relations.string,
                "subj": vocab.subjects.string,
            }
        return vocab, self._vepoch

    def _serve_frame(self, meta, arrays) -> Tuple[dict, Optional[dict]]:
        op = meta.get("op")
        # workers forward their RPC's traceparent so the owner-side spans
        # (coalescer wave, device dispatch) stitch into the same trace
        tp = meta.pop("traceparent", None)
        # workers forward the remaining budget; bind it so the coalescer
        # slot wait and oracle-fallback loop on the owner side stay inside
        # what the worker's client granted.  ONE budget covers the whole
        # batch — items never re-arm their own timers.
        ms = meta.pop("deadline_ms", None)
        # a worker serving X-Keto-Cache: bypass forwards the flag so the
        # owner-side probe/insert (engine pre-dispatch, coalescer) see the
        # bypass too — the escape hatch must hold across the process hop
        bypass = bool(meta.pop("cache_bypass", False))
        with deadline.scope(None if ms is None else ms / 1000.0):
            if bypass:
                with cache_context.scope(bypass=True):
                    return self._serve_op(meta, arrays, op, tp)
            return self._serve_op(meta, arrays, op, tp)

    def _decode_batch(self, meta, arrays):
        """Rebuild the worker's tuple batch from id rows + strings.
        Returns (tuples, vepoch, stale) — stale means the worker sent id
        rows minted against a different vocab epoch and must resend."""
        n = int(meta.get("n", 0))
        pos_ids = meta.get("pos_ids") or []
        pos_str = meta.get("pos_str") or []
        strs = meta.get("tuples") or []
        if not pos_ids and not pos_str and strs:
            # plain all-strings batch with no position map
            pos_str = list(range(len(strs)))
            n = n or len(strs)
        vocab, vepoch = self._vocab_state()
        ids = arrays.get("ids") if arrays else None
        if pos_ids:
            if vocab is None or int(meta.get("vepoch", 0)) != vepoch:
                return None, vepoch, True
            if ids is None or ids.shape != (len(pos_ids), 4):
                raise ValueError("id rows missing or misshapen")
        tuples: List[Optional[RelationTuple]] = [None] * n
        if pos_ids:
            rev = self._rev
            for row, pos in zip(np.asarray(ids, dtype=np.int64), pos_ids):
                ns = rev["ns"](int(row[0]))
                obj = rev["obj"](int(row[1]))
                rel = rev["rel"](int(row[2]))
                subj = rev["subj"](int(row[3]))
                if ns is None or obj is None or rel is None or subj is None:
                    raise ValueError("id row outside the owner vocabulary")
                tuples[int(pos)] = RelationTuple(
                    ns, obj, rel, _decode_subject(subj)
                )
        for s, pos in zip(strs, pos_str):
            tuples[int(pos)] = RelationTuple.from_string(s)
        if any(t is None for t in tuples):
            raise ValueError("batch positions do not cover the batch")
        return tuples, vepoch, False

    def _learn_rows(self, meta, vepoch):
        """Id rows for the string-sent tuples so the worker can mirror
        them: only fully-known rows (no -1 anywhere) are learnable."""
        vocab = self._vocab_obj if vepoch else None
        pos_str = meta.get("pos_str") or []
        strs = meta.get("tuples") or []
        if vocab is None or not strs:
            return [], np.zeros((0, 4), dtype=np.int32)
        if not pos_str:
            pos_str = list(range(len(strs)))
        learn_pos, rows = [], []
        for s, pos in zip(strs, pos_str):
            try:
                t = RelationTuple.from_string(s)
            except Exception:  # noqa: BLE001 - unparseable never mirrors
                continue
            row = (
                vocab.namespaces.lookup(t.namespace),
                vocab.objects.lookup(t.object),
                vocab.relations.lookup(t.relation),
                vocab.subjects.lookup(t.subject.unique_id()),
            )
            if min(row) >= 0:
                learn_pos.append(int(pos))
                rows.append(row)
        return learn_pos, np.asarray(rows, dtype=np.int32).reshape(-1, 4)

    def _serve_op(self, meta, arrays, op, tp):
        r = self.registry
        if op == "check":
            with flightrec.rpc_recording(
                r, "check", traceparent=tp, detail="worker->owner check"
            ):
                t0 = time.perf_counter()
                tuples, vepoch, stale = self._decode_batch(meta, arrays)
                if stale:
                    # the worker's id mirror predates the current vocab:
                    # one extra round trip (strings) re-learns it
                    return {"stale_vocab": vepoch}, None
                flightrec.note_stage("parse", time.perf_counter() - t0)
                eng = r.check_engine()
                depth = int(meta.get("depth", 0))
                # cursor piggyback for the workers' local caches: the store
                # head read BEFORE the compute is a lower bound on the state
                # every verdict in this response is computed from — the
                # engine's dispatch drains the changelog to at least this
                # position (oracle engines read the live store outright).
                # Workers stamp their cache entries with it and advance
                # their staleness fence.
                cur = r.store().log_head
                # the shadow plane lives owner-side only (workers relay):
                # sample worker-routed traffic here, where the verdict and
                # the authoritative store are both in-process
                shadow = r.shadow()
                srow, scur = (
                    shadow.reserve_block(len(tuples))
                    if shadow is not None else (None, 0)
                )
                if len(tuples) == 1:
                    # single-check RPCs from the workers MUST go through
                    # check_is_member: that is the coalescer's enqueue point,
                    # so concurrent singles from every worker merge into one
                    # shared device wave.
                    ok = [bool(eng.check_is_member(tuples[0], depth))]
                    flightrec.note(verdict=ok[0])
                else:
                    batch = getattr(eng, "batch_check", None)
                    if batch is not None:
                        ok = [bool(v) for v in batch(tuples, depth)]
                    else:  # oracle engine: sequential surface only
                        ok = [
                            bool(eng.check_is_member(t, depth))
                            for t in tuples
                        ]
                if srow is not None:
                    shadow.submit(tuples[srow], depth, ok[srow], cursor=scur)
                learn_pos, learn_ids = self._learn_rows(meta, vepoch)
                resp = {
                    "cursor": int(cur),
                    "vepoch": vepoch,
                    "learn_pos": learn_pos,
                    # owner-side span buffer rides home so the worker's
                    # request context shows both processes in one trace
                    "spans": flightrec.export_spans(),
                }
                out = {"ok": np.asarray(ok, dtype=np.uint8)}
                if len(learn_pos):
                    out["learn_ids"] = learn_ids
                return resp, out
        if op == "check_cols":
            # columnar batch: the worker's decoded string columns arrive
            # as packed utf-8 blobs (wire.pack_strcol), become ONE
            # ColumnBlock, and ride the owner's wave as a single column
            # group — no per-item tuple materialization on the hot path
            with flightrec.rpc_recording(
                r, "check", traceparent=tp, detail="worker->owner check_cols"
            ):
                t0 = time.perf_counter()
                cols = {
                    k: wire.unpack_strcol(arrays, k)
                    for k in ("ns", "obj", "rel", "sa", "sb", "sc")
                }
                skind_arr = arrays.get("skind")
                if skind_arr is None:
                    raise ValueError("check_cols frame missing skind")
                skind = [int(v) for v in np.asarray(skind_arr).reshape(-1)]
                block = colmod.ColumnBlock(
                    cols["ns"], cols["obj"], cols["rel"], skind,
                    cols["sa"], cols["sb"], cols["sc"],
                )
                flightrec.note_stage("parse", time.perf_counter() - t0)
                flightrec.note(batch=len(block))
                eng = r.check_engine()
                depth = int(meta.get("depth", 0))
                cur = r.store().log_head
                shadow = r.shadow()
                srow, scur = (
                    shadow.reserve_block(len(block))
                    if shadow is not None else (None, 0)
                )
                # check_block FIRST: the coalescer facade forwards unknown
                # attrs to its inner engine (see handlers._check_block_core)
                cb = (getattr(eng, "check_block", None)
                      or getattr(eng, "batch_check_block", None))
                if cb is not None:
                    allowed, errs = cb(block, depth)
                else:
                    allowed, errs = colmod.block_check_via_tuples(
                        eng, block, depth
                    )
                if srow is not None and srow not in errs:
                    shadow.submit(
                        block[srow], depth, bool(allowed[srow]), cursor=scur
                    )
                resp = {
                    "cursor": int(cur),
                    "errs": [
                        [int(i), str(e),
                         int(getattr(e, "status_code", None) or 500)]
                        for i, e in errs.items()
                    ],
                    "spans": flightrec.export_spans(),
                }
                return resp, {"ok": np.asarray(allowed, dtype=np.uint8)}
        if op == "expand":
            with flightrec.rpc_recording(
                r, "expand", traceparent=tp, detail="worker->owner expand"
            ):
                subject = _decode_subject(meta["subject"])
                tree = r.expand_engine().build_tree(
                    subject, int(meta.get("depth", 0))
                )
                return {
                    "tree": tree.to_json() if tree is not None else None
                }, None
        if op == "list_objects":
            with flightrec.rpc_recording(
                r, "list_objects", traceparent=tp,
                detail="worker->owner list_objects",
            ):
                objs, next_token = r.list_engine().list_objects(
                    meta["namespace"], meta["relation"],
                    _decode_subject(meta["subject"]),
                    page_size=int(meta.get("page_size", 0)),
                    page_token=meta.get("page_token", ""),
                )
                return {
                    "objects": list(objs), "next_page_token": next_token,
                }, None
        if op == "list_subjects":
            with flightrec.rpc_recording(
                r, "list_subjects", traceparent=tp,
                detail="worker->owner list_subjects",
            ):
                subs, next_token = r.list_engine().list_subjects(
                    meta["namespace"], meta["object"], meta["relation"],
                    page_size=int(meta.get("page_size", 0)),
                    page_token=meta.get("page_token", ""),
                )
                return {
                    "subjects": [_encode_subject(s) for s in subs],
                    "next_page_token": next_token,
                }, None
        if op == "barrier":
            # freshness barrier forwarded from a worker: the worker can
            # see the shared store but not the device engine, so the
            # owner runs ensure_fresh (token + mode as wire fields); a
            # StaleSnapshotError (412) rides the ordinary wire-error
            # path and re-raises typed on the worker side
            from ketotpu import consistency

            with flightrec.rpc_recording(
                r, "barrier", traceparent=tp, detail="worker->owner barrier"
            ):
                t0 = time.perf_counter()
                consistency.ensure_fresh(
                    r,
                    meta.get("snaptoken") or None,
                    bool(meta.get("latest")),
                    op=str(meta.get("rpc") or "check"),
                )
                flightrec.note_stage("barrier", time.perf_counter() - t0)
                return {"ok": True}, None
        if op == "repl_bootstrap":
            # warm-standby bootstrap: one frame carries the owner's device
            # projection (the checkpoint codec's flat array dict — no
            # re-projection on the standby), the full store scan, and the
            # changelog tail [cursor, head) so the standby's engine drains
            # forward from the snapshot's cursor exactly as the owner would
            from ketotpu.engine import checkpoint as ckpt

            with flightrec.rpc_recording(
                r, "repl_bootstrap", traceparent=tp,
                detail="standby->owner bootstrap",
            ):
                eng = r._device_engine()
                (snap, cursor, fingerprint, rows, tail, head,
                 version) = eng.replication_snapshot()
                resp_arrays = ckpt.snapshot_to_arrays(
                    snap, extra={"fingerprint": fingerprint},
                    cursor=cursor, head=head, store_version=version,
                )
                wire.pack_tuplecols(resp_arrays, "st", rows)
                wire.pack_changes(resp_arrays, "tl", tail)
                return {
                    "cursor": int(cursor), "head": int(head),
                    "version": int(version),
                    "fingerprint": int(fingerprint),
                    "n_tuples": len(rows),
                }, resp_arrays
        if op == "repl_tail":
            # standby tail poll, doubling as the replication ack: the cursor
            # the standby sends IS its durable head, so acking it here is
            # what releases semi-sync writers waiting in wait_replicated.
            # resync=True mirrors the Watch API's overflow contract — the
            # cursor predates the bounded log and the standby must
            # re-bootstrap from a fresh snapshot.
            if faults.should("tail_drop"):
                raise OSError("fault-injected tail drop")
            cursor = int(meta["cursor"])
            st = r.store()
            if hasattr(st, "changes_since_versioned"):
                entries, head, version = st.changes_since_versioned(cursor)
            else:
                entries, head = st.changes_since(cursor)
                version = st.version
            gate = r.durability_gate()
            if gate is not None:
                gate.ack(cursor)
            resp_arrays = {}
            wire.pack_changes(resp_arrays, "tl", entries or [])
            return {
                "head": int(head), "version": int(version),
                "resync": entries is None,
            }, resp_arrays
        if op == "ping":
            return {"pong": True}, None
        if op == "health":
            # owner-side readiness for the workers' health surface: the
            # worker cannot see the device engine directly, so degraded
            # state (CPU fallback, respawning workers) flows over the wire
            fn = self.health_fn
            return {"health": dict(fn()) if fn is not None else {}}, None
        raise ValueError(f"unknown op {op!r}")

    def stop(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()
        with self._conns_lock:
            conns = list(self._conns)
            self._conns.clear()
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        try:
            os.unlink(self.path)
        except OSError:
            pass


class ReplicationGate:
    """Write-path coupling to the warm-standby follower.

    ``durability.replication`` picks the mode:

    * ``async`` (default) — writes ack as soon as the store commits; the
      standby tails on its own schedule and a takeover may lose the last
      unreplicated entries (bounded by the poll interval);
    * ``semi-sync`` — a write's ack waits until the standby's tail cursor
      covers the committed head.  The standby's ``repl_tail`` poll carries
      its durable head as the cursor, and the owner's handler calls
      ``ack`` with it — that IS the replication acknowledgement.

    The gate only engages once a follower has ATTACHED (first tail poll
    seen): a semi-sync owner with no standby yet — boot order, standby
    restart — must not stall every write forever.  A wait that exceeds
    ``durability.ack_timeout_ms`` degrades that one write to async and
    counts it (``keto_replication_ack_timeouts_total``): availability
    over the durability upgrade, loudly.
    """

    def __init__(self, mode: str = "async", *,
                 ack_timeout_ms: float = 2000.0, metrics=None):
        self.mode = str(mode)
        self.ack_timeout = float(ack_timeout_ms) / 1000.0
        self._metrics = metrics
        self._cond = threading.Condition()
        self._acked = -1
        self._attached = False
        self.timeouts = 0
        self.waits = 0

    def ack(self, cursor: int) -> None:
        """Record the follower's durable head (its tail-poll cursor)."""
        with self._cond:
            self._attached = True
            if cursor > self._acked:
                self._acked = cursor
            self._cond.notify_all()

    def detach(self) -> None:
        """Forget the follower (owner noticed it gone); semi-sync writes
        stop waiting until a follower polls again."""
        with self._cond:
            self._attached = False
            self._cond.notify_all()

    def wait_replicated(self, head: Optional[int]) -> bool:
        """Block a committed write until the follower has acked ``head``.
        True = replicated (or gate not engaged); False = timed out and
        degraded to async for this write."""
        if self.mode != "semi-sync" or head is None:
            return True
        t0 = time.monotonic()
        deadline_at = t0 + self.ack_timeout
        with self._cond:
            if not self._attached:
                return True
            self.waits += 1
            while self._attached and self._acked < head:
                left = deadline_at - time.monotonic()
                if left <= 0:
                    self.timeouts += 1
                    if self._metrics is not None:
                        self._metrics.counter(
                            "keto_replication_ack_timeouts_total", 1,
                            help="semi-sync write acks degraded to async "
                                 "after waiting ack_timeout_ms",
                        )
                    return False
                self._cond.wait(timeout=left)
        if self._metrics is not None:
            self._metrics.observe(
                "keto_replication_wait_seconds",
                time.monotonic() - t0,
                help="time a semi-sync write ack waited for the standby's "
                     "tail cursor to cover it",
            )
        return True

    def stats(self) -> dict:
        with self._cond:
            return {
                "mode": self.mode,
                "attached": self._attached,
                "acked_cursor": self._acked,
                "semi_sync_waits": self.waits,
                "ack_timeouts": self.timeouts,
            }


class _Conn:
    def __init__(self, path, *, metrics=None, shm_threshold: int = 0,
                 connect_timeout: Optional[float] = None):
        # ``path`` is a unix-socket path (the same-host worker wire) or a
        # ``(host, port)`` tuple — the TCP form the cross-host PeerLink
        # lane (parallel/peerlink.py) reuses; the framing discipline
        # (strict one-response-per-request, discard on any transport
        # error) is identical on both transports
        if isinstance(path, tuple):
            self.sock = socket.create_connection(
                path, timeout=connect_timeout
            )
            self.sock.setsockopt(
                socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
            )
            self.sock.settimeout(None)
        else:
            self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            if connect_timeout is not None:
                self.sock.settimeout(connect_timeout)
            self.sock.connect(path)
            self.sock.settimeout(None)
        self.rfile = self.sock.makefile("rb")
        self.lock = threading.Lock()
        self.broken = False
        self._metrics = metrics
        # the shared-memory hop is a SAME-HOST optimization: on TCP the
        # peer is (potentially) another machine, so large payloads stay
        # on the socket and an inbound shm descriptor is a protocol
        # violation (recv_frame with no cache raises WireError)
        self._tcp = isinstance(path, tuple)
        self._shm_threshold = 0 if self._tcp else int(shm_threshold)
        self._ring = None if self._tcp else wire.ShmRing()
        self._shm_cache = None if self._tcp else wire.ShmCache()

    def close(self) -> None:
        self.broken = True
        try:
            self.sock.close()
        except OSError:
            pass
        if self._ring is not None:
            self._ring.close()
        if self._shm_cache is not None:
            self._shm_cache.close()

    def _count(self, direction: str, nbytes: int) -> None:
        if self._metrics is not None:
            self._metrics.counter(
                "keto_wire_bytes_total", float(nbytes),
                help="engine-wire socket bytes by direction",
                dir=direction,
            )

    def call(self, meta, arrays=None,
             timeout: Optional[float] = None) -> Tuple[dict, dict]:
        """One framed request/response on this connection.

        Any transport error — timeout, EOF, framing failure — marks the
        connection broken and closes it: the wire is strictly one
        response per request, so after a partial exchange the NEXT call
        on this socket would read THIS request's late response (the
        desync bug).  Only a decoded typed error keeps the connection —
        the exchange completed, the stream is still aligned.
        """
        if self.broken:
            raise ConnectionError("connection already discarded")
        try:
            with self.lock:
                self.sock.settimeout(timeout)
                sent = wire.send_frame(
                    self.sock, meta, arrays,
                    ring=self._ring, shm_threshold=self._shm_threshold,
                )
                got = wire.recv_frame(self.rfile, shm_cache=self._shm_cache)
            if got is None:
                raise ConnectionError("engine host closed the connection")
            resp, resp_arrays, nread = got
        except Exception:
            self.close()
            raise
        self._count("tx", sent)
        self._count("rx", nread)
        if "error" in resp:
            err = KetoAPIError(resp["error"]["msg"])
            err.status_code = resp["error"].get("status", 500)
            raise err
        return resp, resp_arrays


class RemoteCheckEngine:
    """check.Engine surface forwarding to the device owner's socket.

    A tiny per-thread connection pool: each serving thread keeps its own
    connection (requests on one connection are serialized), so worker
    concurrency maps 1:1 onto owner-side handler threads — which is
    exactly what feeds the owner's coalescer bigger waves.

    Tuples the worker has mirrored ids for ride the wire as packed int32
    rows; the rest go as strings and their ids come back in the response
    (``learn_pos``/``learn_ids``), so steady-state batches are nearly
    all binary.  The owner's vocab EPOCH rides every response; a bump
    (engine vocab swap) resets the mirror, and a ``stale_vocab`` reply
    makes the worker resend that batch as strings.

    Connection errors retry on a fresh connection with capped exponential
    backoff + jitter (the owner may be mid-respawn); a TIMEOUT does not
    retry — the budget is spent and the caller gets DEADLINE_EXCEEDED.
    A batch shares ONE deadline budget across all its items: the budget
    is read once per owner RPC, never re-armed per item."""

    #: reconnect schedule: base*2^n jittered, capped — tuned so a worker
    #: rides out an owner respawn without stampeding the fresh socket
    retry_attempts = 5
    backoff_base = 0.025
    backoff_cap = 0.25

    def __init__(self, path: str, *, rpc_timeout: float = 30.0,
                 cache=None, metrics=None, shm_threshold: int = 262144,
                 breaker_config: Optional[dict] = None,
                 retry_budget_ratio: float = 0.1, logger=None):
        from ketotpu.server.overload import CircuitBreaker, RetryBudget

        self.path = path
        # overload plane, worker-wire lane: the breaker fails calls fast
        # while the owner is down (callers surface the same typed
        # ConnectionError the retry loop would have, without the 5-attempt
        # backoff burn); the retry budget caps reconnect attempts to a
        # fraction of successes so a dead owner cannot multiply load
        self.breaker = CircuitBreaker(
            "worker_wire", metrics=metrics, logger=logger,
            **(breaker_config or {}),
        )
        self.retry_budget = RetryBudget(
            ratio=retry_budget_ratio, lane="worker_wire", metrics=metrics,
        )
        # budget for calls with no request deadline: a wedged owner must
        # surface as an error, not hang every worker thread (<=0 disables)
        self.rpc_timeout = rpc_timeout
        # hot-spot shield, worker side: this process's own ResultCache over
        # the shared store — a hot key answered here never crosses the
        # socket at all.  Verdicts coming back from the owner are stamped
        # with the owner's piggybacked changelog cursor, and that cursor
        # also advances the local staleness fence (the owner broadcasting
        # its drain position to every worker that talks to it).
        self.cache = cache
        self.metrics = metrics
        self.shm_threshold = int(shm_threshold)
        self._flight = SingleFlight(metrics=metrics)
        self.reconnects = 0  # observability: retried transport failures
        self._local = threading.local()
        # vocab mirror shared by every serving thread in this process
        self._mirror_lock = threading.Lock()
        self._mirror_epoch = 0
        self._mirror: dict = {}

    def _conn(self) -> _Conn:
        c = getattr(self._local, "conn", None)
        if c is None or c.broken:
            c = self._local.conn = _Conn(
                self.path, metrics=self.metrics,
                shm_threshold=self.shm_threshold,
            )
        return c

    def _discard(self) -> None:
        c = getattr(self._local, "conn", None)
        if c is not None:
            c.close()
        self._local.conn = None

    def _call(self, meta, arrays=None) -> Tuple[dict, dict]:
        tp = flightrec.current_traceparent()
        if tp:
            meta = dict(meta, traceparent=tp)
        budget = deadline.remaining()
        if budget is not None:
            if budget <= 0:
                raise DeadlineExceededError(
                    "deadline exceeded before owner RPC"
                )
            # forward the remaining budget so the owner bounds ITS waits
            meta = dict(meta, deadline_ms=deadline.deadline_ms())
        timeout = budget
        if timeout is None and self.rpc_timeout > 0:
            timeout = self.rpc_timeout
        if self.metrics is not None:
            self.metrics.counter(
                "keto_wire_calls_total", 1.0,
                help="owner RPC round trips", op=str(meta.get("op")),
            )
        t0 = time.perf_counter()
        try:
            if not self.breaker.allow():
                # lane is open: fail fast into the caller's degrade path
                # instead of burning the full reconnect schedule — the
                # half-open probe will test the owner on the cooldown
                raise ConnectionError(
                    "owner wire circuit breaker open; failing fast"
                )
            last: Optional[BaseException] = None
            for attempt in range(self.retry_attempts):
                try:
                    if faults.should("socket_drop"):
                        self._discard()
                        raise ConnectionError("injected owner-socket drop")
                    resp, resp_arrays = self._conn().call(
                        meta, arrays, timeout=timeout
                    )
                    if isinstance(resp, dict):
                        # owner-side span buffer piggybacks on the reply:
                        # fold it into THIS request's trace so one trace id
                        # covers both processes
                        spans = resp.pop("spans", None)
                        if spans:
                            flightrec.merge_spans(spans)
                    self.breaker.record_success()
                    self.retry_budget.record_success()
                    return resp, resp_arrays
                except KetoAPIError:
                    # a typed error is a COMPLETED exchange — the wire is
                    # healthy even though the verdict is an error
                    self.breaker.record_success()
                    raise
                except TimeoutError:
                    # budget spent waiting on the owner: retrying cannot
                    # beat the deadline, answer DEADLINE_EXCEEDED now
                    self._discard()
                    self.breaker.record_failure()
                    raise DeadlineExceededError(
                        f"owner RPC exceeded {timeout:.3f}s"
                    ) from None
                except (ConnectionError, OSError, ValueError) as e:
                    # ValueError covers a framing failure: the stream
                    # desynced, the connection is already discarded
                    last = e
                    self._discard()
                    self.breaker.record_failure()
                    if attempt + 1 >= self.retry_attempts:
                        break
                    if not self.retry_budget.allow_retry():
                        # retry budget dry: reconnecting now would just
                        # amplify the outage — fail fast instead
                        break
                    self.reconnects += 1
                    delay = min(
                        self.backoff_cap, self.backoff_base * (2 ** attempt)
                    )
                    delay *= 0.5 + random.random() * 0.5  # decorrelate
                    left = deadline.remaining()
                    if left is not None:
                        if left <= 0:
                            raise DeadlineExceededError(
                                "deadline exceeded during owner reconnect"
                            ) from e
                        delay = min(delay, left)
                    time.sleep(delay)
            raise ConnectionError(
                f"owner RPC failed after {attempt + 1} attempts: {last}"
            ) from last
        finally:
            flightrec.note_stage("worker_rpc", time.perf_counter() - t0)

    # -- vocab mirror --------------------------------------------------------

    def _mirror_encode(self, strs: List[str]):
        """Split a batch into mirrored id rows and string leftovers."""
        with self._mirror_lock:
            epoch = self._mirror_epoch
            if not epoch:
                return 0, [], None, list(range(len(strs))), strs
            pos_ids, rows, pos_str, leftovers = [], [], [], []
            for j, s in enumerate(strs):
                row = self._mirror.get(s)
                if row is not None:
                    pos_ids.append(j)
                    rows.append(row)
                else:
                    pos_str.append(j)
                    leftovers.append(s)
        ids = (
            np.asarray(rows, dtype=np.int32).reshape(len(rows), 4)
            if rows else None
        )
        return epoch, pos_ids, ids, pos_str, leftovers

    def _mirror_learn(self, resp, resp_arrays, sent_strs: List[str]) -> None:
        epoch = int(resp.get("vepoch", 0))
        if not epoch:
            return
        learn_pos = resp.get("learn_pos") or []
        learn_ids = (resp_arrays or {}).get("learn_ids")
        with self._mirror_lock:
            if epoch != self._mirror_epoch:
                self._mirror = {}
                self._mirror_epoch = epoch
            if learn_ids is None or not len(learn_pos):
                return
            if len(self._mirror) + len(learn_pos) > _MIRROR_CAP:
                self._mirror = {}
            # learn_pos indexes into the strings WE sent this call; map
            # each back to its canonical form and remember its id row
            pos_to_str = dict(enumerate(sent_strs))
            for row, pos in zip(learn_ids, learn_pos):
                s = pos_to_str.get(int(pos))
                if s is not None:
                    self._mirror[s] = tuple(int(v) for v in row)

    def _mirror_reset(self) -> None:
        with self._mirror_lock:
            self._mirror = {}
            self._mirror_epoch = 0

    # -- check surface -------------------------------------------------------

    def _wire_check(self, strs: List[str], rest_depth: int,
                    bypass: bool) -> Tuple[List[bool], Optional[int]]:
        """One owner round trip for the whole miss-list; id-encodes what
        the mirror knows, learns ids for the rest."""
        epoch, pos_ids, ids, pos_str, leftovers = self._mirror_encode(strs)
        meta = {
            "op": "check",
            "depth": rest_depth,
            "n": len(strs),
            "vepoch": epoch,
            "pos_ids": pos_ids,
            "pos_str": pos_str,
            "tuples": leftovers,
        }
        if bypass:
            meta["cache_bypass"] = True
        arrays = {"ids": ids} if ids is not None else None
        # the position lists index into THIS call's layout; remember the
        # string list actually sent for mirror learning
        resp, resp_arrays = self._call(meta, arrays)
        if resp.get("stale_vocab") is not None:
            # owner swapped vocabularies under our mirror: resend the
            # whole batch as strings (one extra round trip, rare) and
            # relearn from that response
            self._mirror_reset()
            meta = {
                "op": "check",
                "depth": rest_depth,
                "n": len(strs),
                "vepoch": 0,
                "pos_ids": [],
                "pos_str": list(range(len(strs))),
                "tuples": strs,
            }
            if bypass:
                meta["cache_bypass"] = True
            leftovers = strs
            resp, resp_arrays = self._call(meta)
        self._mirror_learn(resp, resp_arrays, leftovers)
        ok_arr = (resp_arrays or {}).get("ok")
        if ok_arr is None:
            ok = [bool(v) for v in resp.get("ok", [])]
        else:
            ok = [bool(v) for v in np.asarray(ok_arr).reshape(-1)]
        if len(ok) != len(strs):
            raise ValueError(
                f"owner answered {len(ok)} verdicts for {len(strs)} tuples"
            )
        cur = resp.get("cursor")
        return ok, (int(cur) if cur is not None else None)

    def batch_check(
        self, queries: Sequence[RelationTuple], rest_depth: int = 0
    ) -> List[bool]:
        if not queries:
            return []
        bypass = cache_context.bypassed()
        cache = None if bypass else self.cache
        results: List[Optional[bool]] = [None] * len(queries)
        miss = list(range(len(queries)))
        if cache is not None:
            hits = cache.lookup_many(
                [cache_check_key(q, rest_depth) for q in queries]
            )
            miss = [i for i, h in enumerate(hits) if h is None]
            for i, h in enumerate(hits):
                if h is not None:
                    results[i] = bool(h.value)
            if len(miss) < len(queries):
                flightrec.note_tier("cache", len(queries) - len(miss))
            if not miss:
                return [bool(v) for v in results]
        ok, cur = self._wire_check(
            [str(queries[i]) for i in miss], rest_depth, bypass,
        )
        if cache is not None and cur is not None:
            cache.advance_fence(int(cur))
            for i, v in zip(miss, ok):
                cache.insert(
                    cache_check_key(queries[i], rest_depth), bool(v), int(cur)
                )
        for i, v in zip(miss, ok):
            results[i] = bool(v)
        return [bool(v) for v in results]

    def batch_check_block(self, block, rest_depth: int = 0):
        """Columnar check surface over the owner wire: the block's string
        columns cross the socket as packed utf-8 blobs in ONE frame
        (wire.pack_strcol) and the verdicts come back as a uint8 array —
        no RelationTuple materialization on either side.

        Same contract as the device engine's ``batch_check_block``:
        ``(allowed bool array, {row: KetoAPIError})``, with the worker's
        local result cache probed first (block.cache_key rows answered
        here never cross the socket) and refilled from the owner's
        piggybacked changelog cursor."""
        n = len(block)
        errs: dict = {}
        allowed = np.zeros(n, dtype=bool)
        if n == 0:
            return allowed, errs
        bypass = cache_context.bypassed()
        cache = None if bypass else self.cache
        miss = list(range(n))
        if cache is not None:
            hits = cache.lookup_many(
                [block.cache_key(i, rest_depth) for i in range(n)]
            )
            miss = [i for i, h in enumerate(hits) if h is None]
            for i, h in enumerate(hits):
                if h is not None:
                    allowed[i] = bool(h.value)
            if len(miss) < n:
                flightrec.note_tier("cache", n - len(miss))
            if not miss:
                return allowed, errs
        sub = block if len(miss) == n else block.take(miss)
        meta = {"op": "check_cols", "depth": int(rest_depth), "n": len(sub)}
        if bypass:
            meta["cache_bypass"] = True
        arrays = {"skind": np.asarray(sub.skind, dtype=np.uint8)}
        for name, col in (("ns", sub.ns), ("obj", sub.obj),
                          ("rel", sub.rel), ("sa", sub.sa),
                          ("sb", sub.sb), ("sc", sub.sc)):
            wire.pack_strcol(arrays, name, col)
        try:
            resp, resp_arrays = self._call(meta, arrays)
        except DeadlineExceededError:
            raise
        except KetoAPIError as e:
            if int(getattr(e, "status_code", 0) or 0) == 504:
                # the owner's deadline expiry crossed the wire as a plain
                # typed error; re-raise it as the batch-wide expiry the
                # handler's per-item 504 fan-out expects
                raise DeadlineExceededError(str(e)) from e
            raise
        ok = (resp_arrays or {}).get("ok")
        if ok is None or len(np.asarray(ok).reshape(-1)) != len(sub):
            raise ValueError(
                f"owner answered {0 if ok is None else len(ok)} verdicts "
                f"for {len(sub)} tuples"
            )
        ok = np.asarray(ok).reshape(-1)
        sub_errs: dict = {}
        for row, msg, status in resp.get("errs") or []:
            e = KetoAPIError(str(msg))
            e.status_code = int(status)
            sub_errs[int(row)] = e
        cur = resp.get("cursor")
        if cache is not None and cur is not None:
            cache.advance_fence(int(cur))
        for j, i in enumerate(miss):
            e = sub_errs.get(j)
            if e is not None:
                errs[i] = e  # errored rows never reach the cache
                continue
            v = bool(ok[j])
            allowed[i] = v
            if cache is not None and cur is not None:
                cache.insert(block.cache_key(i, rest_depth), v, int(cur))
        return allowed, errs

    def check(self, r: RelationTuple, rest_depth: int = 0) -> bool:
        return self.batch_check([r], rest_depth)[0]

    def check_is_member(self, r: RelationTuple, rest_depth: int = 0) -> bool:
        if cache_context.bypassed():
            return self.check(r, rest_depth)
        # worker-side singleflight: a thundering herd on one hot key in
        # THIS process collapses to one owner RPC; followers park
        # deadline-aware and share the leader's verdict (the leader's
        # batch_check also lands it in the local cache for the next wave)
        value, _led = self._flight.do(
            (str(r), int(rest_depth)),
            lambda: self.check(r, rest_depth),
            default_timeout=self.rpc_timeout if self.rpc_timeout > 0 else None,
        )
        return bool(value)

    def consistency_barrier(
        self, snaptoken: Optional[str] = None, latest: bool = False,
        op: str = "check",
    ) -> None:
        """Run the freshness barrier on the device owner
        (ketotpu/consistency/barrier.py routes here when the engine is
        remote).  Raises the owner's typed refusal — StaleSnapshotError
        412 — through the wire-error path."""
        meta = {"op": "barrier", "rpc": op}
        if snaptoken:
            meta["snaptoken"] = snaptoken
        if latest:
            meta["latest"] = True
        self._call(meta)


class RemoteExpandEngine:
    """expand.Engine surface forwarding to the device owner."""

    def __init__(self, path: str, check: Optional[RemoteCheckEngine] = None):
        self._remote = check if check is not None else RemoteCheckEngine(path)

    def build_tree(self, subject: Subject, max_depth: int = 0) -> Optional[Tree]:
        resp, _ = self._remote._call({
            "op": "expand",
            "subject": _encode_subject(subject),
            "depth": max_depth,
        })
        if resp["tree"] is None:
            return None
        return Tree.from_json(resp["tree"])


class RemoteListEngine:
    """Listing-engine surface forwarding to the device owner (the Leopard
    closure index lives with the device; workers only relay)."""

    def __init__(self, path: str, check: Optional[RemoteCheckEngine] = None):
        self._remote = check if check is not None else RemoteCheckEngine(path)

    def list_objects(
        self, namespace: str, relation: str, subject: Subject,
        *, page_size: int = 0, page_token: str = "",
    ):
        resp, _ = self._remote._call({
            "op": "list_objects",
            "namespace": namespace,
            "relation": relation,
            "subject": _encode_subject(subject),
            "page_size": page_size,
            "page_token": page_token,
        })
        return list(resp["objects"]), resp.get("next_page_token", "")

    def list_subjects(
        self, namespace: str, object: str, relation: str,
        *, page_size: int = 0, page_token: str = "",
    ):
        resp, _ = self._remote._call({
            "op": "list_subjects",
            "namespace": namespace,
            "object": object,
            "relation": relation,
            "page_size": page_size,
            "page_token": page_token,
        })
        subs = [_decode_subject(u) for u in resp["subjects"]]
        return subs, resp.get("next_page_token", "")


def engine_host_readiness(path: str, timeout: float = 1.0):
    """Readiness-check factory for worker registries: probe the owner.

    Unreachable owner -> raise (the worker cannot serve checks at all);
    reachable owner with degraded health values -> return the degraded
    string so the worker's health surface mirrors the owner's.
    """

    def probe():
        conn = _Conn(path)
        try:
            resp, _ = conn.call({"op": "health"}, timeout=timeout)
        finally:
            conn.close()
        health = resp.get("health", {})
        bad = {k: v for k, v in health.items() if v != "ok"}
        if not bad:
            return "ok"
        if all(str(v).startswith("degraded") for v in bad.values()):
            return "degraded: owner " + "; ".join(
                f"{k}={v}" for k, v in sorted(bad.items())
            )
        raise ConnectionError(
            "owner unhealthy: " + "; ".join(
                f"{k}={v}" for k, v in sorted(bad.items())
            )
        )

    return probe


class WorkerSupervisor:
    """Respawn dead serve processes with capped backoff + jitter.

    ``serve --workers`` hands this every worker subprocess (and polls the
    owner's engine-host thread itself).  A dead worker is respawned after
    a jittered backoff that grows with its recent death count; while any
    respawn is pending the supervisor's ``state()`` reports ``degraded``
    (surfaced through health + ``status --block``).  A worker that keeps
    dying — ``max_rapid_deaths`` exits inside ``rapid_window`` seconds —
    makes the supervisor give up (``poll`` returns an exit code) instead
    of flapping forever: at that point the failure is systemic, not
    transient.
    """

    def __init__(
        self,
        spawn: Callable[[int], "subprocess.Popen"],
        count: int,
        *,
        max_rapid_deaths: int = 5,
        rapid_window: float = 30.0,
        backoff_base: float = 0.5,
        backoff_cap: float = 5.0,
        log: Optional[Callable[[str], None]] = None,
    ):
        self._spawn = spawn
        self.count = count
        self.max_rapid_deaths = max_rapid_deaths
        self.rapid_window = rapid_window
        self.backoff_base = backoff_base
        self.backoff_cap = backoff_cap
        self._log = log or (lambda msg: None)
        self.procs: List[Optional["subprocess.Popen"]] = [None] * count
        self.respawns = 0  # observability: successful respawn count
        self._deaths: List[float] = []  # monotonic stamps, pruned to window
        self._death_counts = [0] * count
        self._respawn_at: List[Optional[float]] = [None] * count

    def start(self) -> "WorkerSupervisor":
        for i in range(self.count):
            self.procs[i] = self._spawn(i)
        return self

    def _record_death(self, i: int, rc) -> Optional[int]:
        now = time.monotonic()
        self._deaths.append(now)
        self._deaths = [t for t in self._deaths if now - t < self.rapid_window]
        self._death_counts[i] += 1
        if len(self._deaths) >= self.max_rapid_deaths:
            self._log(
                f"worker {i} exited rc={rc}; {len(self._deaths)} deaths in "
                f"{self.rapid_window:.0f}s — giving up"
            )
            return 1
        delay = min(
            self.backoff_cap,
            self.backoff_base * (2 ** (self._death_counts[i] - 1)),
        )
        delay *= 0.5 + random.random() * 0.5
        self._respawn_at[i] = now + delay
        self._log(
            f"worker {i} exited rc={rc}; respawning in {delay:.1f}s"
        )
        return None

    def poll(self) -> Optional[int]:
        """One supervision step. Returns an exit code to give up with,
        or None to keep serving."""
        now = time.monotonic()
        for i, p in enumerate(self.procs):
            if p is not None and p.poll() is not None:
                rc = self._record_death(i, p.returncode)
                if rc is not None:
                    return rc
                self.procs[i] = None
            if self.procs[i] is None and self._respawn_at[i] is not None:
                if now >= self._respawn_at[i]:
                    self._respawn_at[i] = None
                    self.procs[i] = self._spawn(i)
                    self.respawns += 1
                    self._log(f"worker {i} respawned")
        return None

    def state(self) -> str:
        """Health-check value: 'ok', or 'degraded: ...' while respawning."""
        down = [
            i for i, p in enumerate(self.procs)
            if p is None or p.poll() is not None
        ]
        if not down:
            return "ok"
        return "degraded: respawning worker(s) " + ",".join(map(str, down))

    def terminate(self) -> None:
        for p in self.procs:
            if p is not None and p.poll() is None:
                p.terminate()
        for p in self.procs:
            if p is not None:
                try:
                    p.wait(timeout=5)
                except Exception:  # noqa: BLE001
                    p.kill()
