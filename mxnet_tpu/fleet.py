"""Multi-replica serving fleet: elastic router, admission control,
zero-downtime weight swap.

One engine process serves one chip's worth of streams and dies whole:
a crash drops every in-flight request, and a weight update means
downtime.  This module is the fleet tier above ``serving.py`` —
Orca-style iteration-level serving extended from one scheduler to a
routed fleet:

* the **Router** speaks the ``wire.py`` length-prefixed frame protocol
  (the ``ps.py`` wire — shared primitives, shared HMAC discipline for
  structured payloads) to clients, and spreads requests over N engine
  **replicas**, each a process wrapping an ``InferenceEngine`` or
  ``DecodeEngine`` behind a :class:`ReplicaHarness`;
* **health** is the PR-8 heartbeat-file machinery re-used verbatim:
  every replica runs an ``elastic.HeartbeatWriter``, the router's
  monitor runs the ``elastic.stale_ids`` staleness scan (missing or
  stale = dead, future mtimes = alive), and a transport failure is
  cross-checked against staleness before conviction;
* a dead replica's in-flight requests are transparently **retried** on
  a survivor.  Exactly-once is the PR-3 ticket discipline applied at
  the delivery edge: a ticket retires only when its response reaches
  the client, a retry is dispatched only for unretired tickets, and a
  zombie's late answer finds its ticket retired and is dropped
  (counted, never double-delivered).  Decode retries are **bit-exact**:
  the router stamps every decode request with a deterministic sampling
  seed, and replicas share the engine seed, so a survivor re-samples
  exactly the tokens the dead replica would have produced — no
  already-delivered token is ever re-sampled differently;
* **admission control + deadline shedding**: the router tracks
  per-replica queue depth and a PR-1-style learned per-bucket cost
  model (EMA of measured service time per work-unit bucket).  A
  request that provably cannot meet its deadline fails with a typed
  :class:`ShedError`; under overload the pending queue sheds
  oldest-deadline-first instead of letting p99 run away;
* :meth:`Router.swap_weights` is the **zero-downtime rolling update**:
  replicas drain one at a time (the rest keep serving), load the
  newest committed, checksum-verified checkpoint
  (``checkpoint.load_latest_params`` — a training run's checkpoint
  root or a ``checkpoint.publish_params`` output), warm up, and
  re-admit.  A swap drops zero requests.

Wire security matches ``ps.py``: tensor frames are never pickled, and
every structured control payload (drain/swap/stop) carries an
HMAC-SHA256 keyed by the launcher-distributed secret, verified before
parsing.

See README "Multi-replica serving" for the architecture diagram and
failure model; ``tools/bench_fleet.py`` runs the closed-loop sweep and
the kill-one-replica acceptance drill.
"""

from __future__ import annotations

import json
import logging
import os
import socket
import socketserver
import struct
import subprocess
import sys
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import adapters as _adapters
from . import profiler
from . import slo as _slo
from . import wire
from .base import MXNetError
from .elastic import (HeartbeatWriter, dead_rank_timeout,
                      heartbeat_interval, stale_ids, _validated_env)

__all__ = ["Router", "FleetClient", "ShedError", "ReplicaClient",
           "ReplicaServer", "spawn_replica", "launch_local_fleet",
           "read_endpoint", "write_secret", "read_secret"]

# fleet wire ops (a separate op space from ps.py: different servers,
# same framing)
(_F_SUBMIT, _F_RESULT, _F_CTRL, _F_CTRL_RESULT,
 _F_MIGRATE, _F_ADAPTER) = range(101, 107)

# disaggregated-serving replica roles ("mixed" = the classic
# do-everything replica); the fleet is DISAGGREGATED the moment both
# specialized roles are present
REPLICA_ROLES = ("prefill", "decode", "mixed")

# result status bytes
_ST_OK, _ST_ERR, _ST_SHED = 0, 1, 2

_K_INFER, _K_DECODE = 0, 1
_NO_EOS = -(1 << 62)

_log = logging.getLogger("mxnet_tpu.fleet")

# ShedError-burst flight-recorder trigger: this many sheds inside the
# window = one post-mortem dump (rate-limited in dump_flight_record)
_SHED_BURST_COUNT = 32
_SHED_BURST_WINDOW_S = 10.0


class ShedError(MXNetError):
    """Typed admission-control rejection: the router determined this
    request cannot (or should not) be served within its deadline —
    shed NOW so the client can fail over / degrade, instead of
    discovering the miss after the deadline already passed.  Carries
    ``reason`` ('deadline' | 'expired' | 'overload')."""

    def __init__(self, msg: str, reason: str = "deadline"):
        self.reason = reason
        super().__init__(msg)


def fleet_env(name: str):
    """MXNET_FLEET_* with loud at-construction validation (the
    MXNET_CKPT_* pattern): garbage raises, defaults resolve through
    the config catalog."""
    minima = {"MXNET_FLEET_REPLICAS": 1,
              "MXNET_FLEET_SHED_DEADLINE_MS": 0.0,
              "MXNET_FLEET_RETRY_BUDGET": 0,
              "MXNET_FLEET_SWAP_DRAIN_TIMEOUT": 0.1,
              "MXNET_FLEET_AUTOSCALE": 0,
              "MXNET_FLEET_AUTOSCALE_INTERVAL": 0.05}
    return _validated_env(name, minimum=minima[name])


def roles_env() -> Optional[List[str]]:
    """``MXNET_FLEET_ROLES`` — comma-separated initial role per replica
    (by rid order), e.g. ``prefill,decode,decode``.  Empty/unset =
    roles never enabled (the classic mixed fleet).  Garbage raises at
    construction, and a split that names one specialized role without
    its counterpart is refused: a prefill-only fleet can never decode,
    and vice versa."""
    raw = os.environ.get("MXNET_FLEET_ROLES", "").strip()
    if not raw:
        return None
    roles = [tok.strip() for tok in raw.split(",")]
    for tok in roles:
        if tok not in REPLICA_ROLES:
            raise MXNetError(
                f"MXNET_FLEET_ROLES={raw!r}: role {tok!r} must be one "
                f"of {REPLICA_ROLES}")
    if ("prefill" in roles) != ("decode" in roles):
        raise MXNetError(
            f"MXNET_FLEET_ROLES={raw!r}: a disaggregated fleet needs "
            "BOTH a prefill and a decode role (or neither) — a "
            "one-sided split cannot serve a single request end to end")
    return roles


# ---------------------------------------------------------------------------
# spec <-> wire
# ---------------------------------------------------------------------------


def _pack_spec(spec: Dict[str, Any]) -> bytes:
    """Request payload: tensors ride the wire encoding, never pickle."""
    if spec["kind"] == "infer":
        inputs = spec["inputs"]
        if len(inputs) > 0xFFFF:
            raise MXNetError("too many inputs for one request")
        body = bytearray([_K_INFER])
        body += struct.pack("!H", len(inputs))
        for name, arr in inputs.items():
            body += wire.pack_key(name)
            body += wire.pack_tensor(np.asarray(arr))
        return bytes(body)
    if spec["kind"] == "decode":
        body = bytearray([_K_DECODE])
        body += wire.U32.pack(int(spec["max_new"]))
        temp = spec.get("temperature")
        body += struct.pack("!d", -1.0 if temp is None else float(temp))
        eos = spec.get("eos")
        body += wire.I64.pack(_NO_EOS if eos is None else int(eos))
        body += wire.U64.pack(int(spec.get("seed", 0)))
        # disagg phase byte: 0 = classic end-to-end decode, 1 =
        # prefill-export (the response is a signed KV page frame)
        body += struct.pack("!B", 1 if spec.get("phase") == 1 else 0)
        # tenancy triplet (PR 20): SLO class rides to the replica so
        # engine-side admission can tier; tenant/adapter name the
        # quota bucket and the LoRA slot ("" = not set)
        body += wire.pack_key(spec.get("slo_class") or "interactive")
        body += wire.pack_key(spec.get("tenant") or "")
        body += wire.pack_key(spec.get("adapter") or "")
        body += wire.pack_tensor(
            np.asarray(spec["prompt"], dtype=np.int32))
        return bytes(body)
    raise MXNetError(f"unknown request kind {spec['kind']!r}")


def _unpack_spec(buf: memoryview, off: int) -> Dict[str, Any]:
    kind = buf[off]
    off += 1
    if kind == _K_INFER:
        (n,) = struct.unpack_from("!H", buf, off)
        off += 2
        inputs = {}
        for _ in range(n):
            name, off = wire.unpack_key(buf, off)
            arr, off = wire.unpack_tensor(buf, off)
            inputs[name] = np.array(arr)  # own the buffer
        return {"kind": "infer", "inputs": inputs}
    if kind == _K_DECODE:
        (max_new,) = wire.U32.unpack_from(buf, off)
        off += 4
        (temp,) = struct.unpack_from("!d", buf, off)
        off += 8
        (eos,) = wire.I64.unpack_from(buf, off)
        off += 8
        (seed,) = wire.U64.unpack_from(buf, off)
        off += 8
        phase = buf[off]
        off += 1
        slo_class, off = wire.unpack_key(buf, off)
        tenant, off = wire.unpack_key(buf, off)
        adapter, off = wire.unpack_key(buf, off)
        prompt, off = wire.unpack_tensor(buf, off)
        return {"kind": "decode", "prompt": np.array(prompt),
                "max_new": int(max_new),
                "temperature": None if temp < 0 else float(temp),
                "eos": None if eos == _NO_EOS else int(eos),
                "seed": int(seed), "phase": int(phase),
                "slo_class": slo_class or "interactive",
                "tenant": tenant or None,
                "adapter": adapter or None}
    raise MXNetError(f"unknown wire request kind {kind}")


def _pack_result(result) -> bytes:
    """infer → list of output arrays; decode → one int32 token array."""
    if isinstance(result, np.ndarray):
        result = [result]
    if len(result) > 0xFFFF:
        raise MXNetError("too many outputs for one response")
    body = bytearray(struct.pack("!H", len(result)))
    for arr in result:
        body += wire.pack_tensor(np.asarray(arr))
    return bytes(body)


def _unpack_result(buf: memoryview, off: int) -> List[np.ndarray]:
    (n,) = struct.unpack_from("!H", buf, off)
    off += 2
    out = []
    for _ in range(n):
        arr, off = wire.unpack_tensor(buf, off)
        out.append(np.array(arr))
    return out


# ---------------------------------------------------------------------------
# duplex connection: frames tagged by request id, responses out of order
# ---------------------------------------------------------------------------


class _Duplex:
    """One socket, many in-flight requests.  Unlike the PS client's
    FIFO ticket pipeline (one server thread per connection answers in
    order), fleet responses complete OUT of order — a decode retires
    whenever its stream does — so every frame carries a request id and
    a reader thread matches responses to futures."""

    def __init__(self, sock: socket.socket, name: str):
        self._sock = sock
        self._name = name
        self._wlock = threading.Lock()
        self._lock = threading.Lock()
        self._futures: Dict[int, Future] = {}
        self._next_id = 0
        self._dead: Optional[BaseException] = None
        self._on_death = None  # callback(exc), set before start()
        self._reader = threading.Thread(
            target=self._read_loop, daemon=True,
            name=f"mxnet_tpu-fleet-{name}")

    def start(self):
        self._reader.start()

    def begin(self, op: int, body: bytes, parse, tear=None) -> Future:
        """Send ``op | req_id | body``; the Future resolves with
        ``parse(status, payload_view)`` when the matching response
        arrives.  A dead connection fails ALL outstanding futures.

        ``tear``: optional chaos hook ``tear(sock, frame) -> bool`` —
        when it returns True it has destroyed the connection mid-frame
        (half the bytes sent, socket shut down); the send is treated
        as a transport death, exactly like a peer crashing mid-write."""
        fut: Future = Future()
        with self._lock:
            if self._dead is not None:
                raise MXNetError(
                    f"fleet connection {self._name} is dead: "
                    f"{self._dead}") from self._dead
            rid = self._next_id
            self._next_id += 1
            self._futures[rid] = fut
        fut._fleet_parse = parse  # type: ignore[attr-defined]
        frame = bytes([op]) + wire.U64.pack(rid) + body
        try:
            with self._wlock:
                if tear is not None and tear(self._sock, frame):
                    raise ConnectionError(
                        "chaos: migration frame torn mid-send")
                wire.send_frame(self._sock, frame)
        except BaseException as exc:
            self._poison(exc)
            raise
        return fut

    def _read_loop(self):
        try:
            while True:
                resp = wire.recv_frame(self._sock)
                (rid,) = wire.U64.unpack_from(resp, 1)
                status = resp[9]
                with self._lock:
                    fut = self._futures.pop(rid, None)
                if fut is None:
                    continue  # cancelled/unknown — drop
                parse = getattr(fut, "_fleet_parse", None)
                try:
                    val = parse(status, memoryview(resp)[10:])
                except BaseException as exc:  # noqa: BLE001
                    if fut.set_running_or_notify_cancel():
                        fut.set_exception(exc)
                    continue
                if fut.set_running_or_notify_cancel():
                    if isinstance(val, BaseException):
                        fut.set_exception(val)
                    else:
                        fut.set_result(val)
        except BaseException as exc:  # noqa: BLE001 — poison and exit
            self._poison(exc)

    def _poison(self, exc: BaseException):
        with self._lock:
            if self._dead is None:
                self._dead = exc
            futures, self._futures = self._futures, {}
        for fut in futures.values():
            if fut.set_running_or_notify_cancel():
                fut.set_exception(ConnectionError(
                    f"fleet connection {self._name} died: {exc}"))
        cb = self._on_death
        if cb is not None:
            try:
                cb(exc)
            except Exception:  # noqa: BLE001 — observer only
                pass

    @property
    def dead(self) -> Optional[BaseException]:
        return self._dead

    def close(self):
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass


def _parse_submit_response(status: int, payload: memoryview):
    if status == _ST_OK:
        return _unpack_result(payload, 0)
    msg = bytes(payload).decode(errors="replace")
    if status == _ST_SHED:
        head, _, detail = msg.partition(":")
        return ShedError(detail.strip() or msg, reason=head or "deadline")
    return MXNetError(msg)


def _make_page_frame_parser(secret: bytes):
    """Response parser for a phase-1 (prefill-export) submit: the ok
    payload is one signed KV page frame.  The router verifies it here
    and keeps the RAW bytes too — the forward to the decode replica
    ships the already-signed frame verbatim (same fleet secret), so a
    megabyte of page slabs is never re-encoded in the hot handoff."""

    def parse(status: int, payload: memoryview):
        if status == _ST_OK:
            frame = bytes(payload)
            meta, arrays = wire.unpack_page_frame(
                secret, memoryview(frame), "migration frame (prefill)")
            return {"meta": meta, "arrays": arrays, "frame": frame}
        return _parse_submit_response(status, payload)

    return parse


# ---------------------------------------------------------------------------
# replica side: TCP server over a ReplicaHarness
# ---------------------------------------------------------------------------


class ReplicaServer:
    """Serve ONE :class:`serving.ReplicaHarness` on the fleet wire.

    SUBMIT frames feed the engine; the response frame is written from
    the engine future's done-callback (out-of-order completion — a
    per-connection write lock keeps frames whole).  CTRL frames
    (signed JSON: drain / resume / swap / inflight / stats / stop) run
    on a worker thread so a long drain never stalls the response
    stream it is waiting on.  The server heartbeats
    ``<fleet_dir>/hb_<rid>`` — the PR-8 liveness plane."""

    def __init__(self, harness, rid: int, fleet_dir: Optional[str] = None,
                 secret: bytes = b"", host: str = "127.0.0.1",
                 port: int = 0):
        self.harness = harness
        self.rid = int(rid)
        self._secret = secret
        self._closing = threading.Event()
        self._hb = None
        if fleet_dir:
            self._hb = HeartbeatWriter(fleet_dir, self.rid,
                                       chaos_ident=self.rid)
        server_self = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                wlock = threading.Lock()
                try:
                    while True:
                        req = wire.recv_frame(self.request)
                        server_self._dispatch(req, self.request, wlock)
                except (ConnectionError, EOFError, OSError):
                    pass

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name=f"mxnet_tpu-fleet-replica-{rid}")
        self._thread.start()

    def _send(self, sock, wlock, op: int, rid: int, status: int,
              payload: bytes):
        frame = bytes([op]) + wire.U64.pack(rid) + bytes([status]) \
            + payload
        try:
            with wlock:
                wire.send_frame(sock, frame)
        except OSError:
            pass  # connection died; the router convicts via heartbeat

    def _dispatch(self, buf: memoryview, sock, wlock):
        op = buf[0]
        (rid,) = wire.U64.unpack_from(buf, 1)
        if op == _F_SUBMIT:
            try:
                # optional trace field first (PR 12): the router's
                # span becomes the parent of this replica's spans
                trace, off = wire.unpack_trace(buf, 9)
                if trace is not None:
                    profiler.trace_point(
                        "wire.recv", trace.child(), cat="fleet",
                        args={"rid": self.rid})
                spec = _unpack_spec(buf, off)
                prefill = spec["kind"] == "decode" and spec.get("phase")
                if spec["kind"] == "infer":
                    fut = self.harness.submit_infer(spec["inputs"],
                                                    trace=trace)
                elif prefill:
                    fut = self.harness.submit_prefill_export(
                        spec["prompt"], spec["max_new"],
                        temperature=spec["temperature"],
                        eos_id=spec["eos"], seed=spec["seed"],
                        trace=trace,
                        slo_class=spec.get("slo_class",
                                           "interactive"),
                        tenant=spec.get("tenant"),
                        adapter=spec.get("adapter"))
                else:
                    fut = self.harness.submit_decode(
                        spec["prompt"], spec["max_new"],
                        temperature=spec["temperature"],
                        eos_id=spec["eos"], seed=spec["seed"],
                        trace=trace,
                        slo_class=spec.get("slo_class",
                                           "interactive"),
                        tenant=spec.get("tenant"),
                        adapter=spec.get("adapter"))
            except BaseException as exc:  # noqa: BLE001 — to the wire
                self._send(sock, wlock, _F_RESULT, rid, _ST_ERR,
                           f"{type(exc).__name__}: {exc}".encode())
                return

            def done(f, _rid=rid, _prefill=prefill):
                exc = f.exception()
                if exc is not None:
                    self._send(sock, wlock, _F_RESULT, _rid, _ST_ERR,
                               f"{type(exc).__name__}: {exc}".encode())
                elif _prefill:
                    # the result is a migration payload: sign it whole
                    # (meta AND slabs) — the router forwards these
                    # bytes verbatim to the decode-role replica
                    pay = f.result()
                    self._send(sock, wlock, _F_RESULT, _rid, _ST_OK,
                               wire.pack_page_frame(
                                   self._secret, pay["meta"],
                                   pay["kv_arrays"]))
                else:
                    self._send(sock, wlock, _F_RESULT, _rid, _ST_OK,
                               _pack_result(f.result()))

            fut.add_done_callback(done)
            return
        if op == _F_MIGRATE:
            try:
                trace, off = wire.unpack_trace(buf, 9)
                if trace is not None:
                    profiler.trace_point(
                        "wire.recv", trace.child(), cat="fleet",
                        args={"rid": self.rid, "op": "migrate"})
                meta, arrays = wire.unpack_page_frame(
                    self._secret, buf[off:], "migration frame (import)")
                fut = self.harness.submit_import(meta, arrays,
                                                 trace=trace)
            except BaseException as exc:  # noqa: BLE001 — to the wire
                self._send(sock, wlock, _F_RESULT, rid, _ST_ERR,
                           f"{type(exc).__name__}: {exc}".encode())
                return

            def mig_done(f, _rid=rid):
                exc = f.exception()
                if exc is not None:
                    self._send(sock, wlock, _F_RESULT, _rid, _ST_ERR,
                               f"{type(exc).__name__}: {exc}".encode())
                else:
                    self._send(sock, wlock, _F_RESULT, _rid, _ST_OK,
                               _pack_result(f.result()))

            fut.add_done_callback(mig_done)
            return
        if op == _F_ADAPTER:
            # hot LoRA publish: tensors ride the signed page-frame
            # encoding (never pickle, same HMAC discipline as
            # migration payloads); runs inline — a slab write is
            # milliseconds and must not race a second publish of the
            # same name through another thread
            try:
                _trace, off = wire.unpack_trace(buf, 9)
                meta, arrays = wire.unpack_page_frame(
                    self._secret, buf[off:], "adapter frame (publish)")
                if len(arrays) != 2:
                    raise MXNetError(
                        f"adapter frame carries {len(arrays)} arrays; "
                        "expected [a, b]")
                slot = self.harness.publish_adapter(
                    meta["name"], arrays[0], arrays[1],
                    alpha=meta.get("alpha"))
                self._send(sock, wlock, _F_RESULT, rid, _ST_OK,
                           json.dumps({"slot": int(slot)}).encode())
            except BaseException as exc:  # noqa: BLE001 — to the wire
                self._send(sock, wlock, _F_RESULT, rid, _ST_ERR,
                           f"{type(exc).__name__}: {exc}".encode())
            return
        if op == _F_CTRL:
            try:
                _trace, off = wire.unpack_trace(buf, 9)
                spec, _ = wire.unpack_signed_json(
                    self._secret, buf, off, "fleet control frame")
            except BaseException as exc:  # noqa: BLE001 — to the wire
                self._send(sock, wlock, _F_CTRL_RESULT, rid, _ST_ERR,
                           f"{type(exc).__name__}: {exc}".encode())
                return
            threading.Thread(
                target=self._ctrl, args=(spec, rid, sock, wlock),
                daemon=True,
                name=f"mxnet_tpu-fleet-ctrl-{spec.get('op')}").start()
            return
        self._send(sock, wlock, _F_RESULT, rid, _ST_ERR,
                   f"unknown fleet op {op}".encode())

    def _ctrl(self, spec: Dict, rid: int, sock, wlock):
        try:
            op = spec.get("op")
            if op == "inflight":
                out: Any = {"inflight": self.harness.inflight()}
            elif op == "stats":
                out = self.harness.stats()
            elif op == "drain":
                out = {"inflight": self.harness.drain(
                    timeout=float(spec.get("timeout", 30.0)))}
            elif op == "resume":
                self.harness.resume()
                out = {"ok": True}
            elif op == "swap":
                out = self.harness.swap(
                    spec["ckpt_dir"],
                    drain_timeout=float(spec.get("drain_timeout", 60.0)))
            elif op == "role":
                self.harness.set_role(spec["role"])
                out = {"ok": True, "role": spec["role"]}
            elif op == "retire_adapter":
                out = {"freed": bool(
                    self.harness.retire_adapter(spec["name"]))}
            elif op == "stop":
                out = {"ok": True}
                self._closing.set()
            else:
                raise MXNetError(f"unknown fleet control op {op!r}")
            self._send(sock, wlock, _F_CTRL_RESULT, rid, _ST_OK,
                       json.dumps(out).encode())
        except BaseException as exc:  # noqa: BLE001 — to the wire
            self._send(sock, wlock, _F_CTRL_RESULT, rid, _ST_ERR,
                       f"{type(exc).__name__}: {exc}".encode())
        if self._closing.is_set():
            self.close()

    def wait_closed(self, timeout: Optional[float] = None) -> bool:
        return self._closing.wait(timeout)

    def close(self):
        self._closing.set()
        threading.Thread(target=self._server.shutdown,
                         daemon=True).start()
        self._server.server_close()
        if self._hb is not None:
            self._hb.stop(remove=True)
        self.harness.close()


class ReplicaClient:
    """Router-side handle to a (remote) replica: the duck type the
    Router schedules over — in-process fakes in the tests implement
    the same surface without a socket."""

    def __init__(self, rid: int, host: str, port: int,
                 secret: bytes = b"", timeout: float = 30.0):
        self.rid = int(rid)
        t0 = time.monotonic()
        while True:
            try:
                sock = socket.create_connection((host, port), timeout=10)
                break
            except OSError:
                if time.monotonic() - t0 > timeout:
                    raise MXNetError(
                        f"cannot reach replica {rid} at {host}:{port}")
                time.sleep(0.1)
        sock.settimeout(None)
        self._secret = secret
        self._dx = _Duplex(sock, f"replica-{rid}")
        self._dx.start()

    def set_on_death(self, cb):
        self._dx._on_death = cb

    @property
    def transport_dead(self) -> Optional[BaseException]:
        return self._dx.dead

    def submit(self, spec: Dict[str, Any]) -> Future:
        # "trace" is router metadata, not request payload: it rides
        # the frame's optional trace field, never the spec encoding
        trace = spec.get("trace")
        if trace is not None:
            spec = {k: v for k, v in spec.items() if k != "trace"}
        if spec["kind"] == "migrate":
            return self._submit_migrate(spec, trace)
        body = wire.pack_trace(trace) + _pack_spec(spec)
        parse = (_make_page_frame_parser(self._secret)
                 if spec["kind"] == "decode" and spec.get("phase")
                 else _parse_submit_response)
        t0 = time.perf_counter()
        fut = self._dx.begin(_F_SUBMIT, body, parse)
        if trace is not None:
            profiler.add_trace_event(
                "wire.send", t0, time.perf_counter() - t0,
                trace.child(), cat="fleet",
                args={"rid": self.rid, "bytes": len(body)})
        return fut

    def _submit_migrate(self, spec: Dict[str, Any], trace) -> Future:
        """Phase 2: forward the prefill replica's already-signed page
        frame to this (decode-role) replica.  The Future resolves to
        the FULL generated token list once the migrated stream retires
        there.  ``MXNET_CHAOS_MIGRATION_TEAR`` hooks THIS send — the
        drill tears the Nth migration frame mid-flight and the ticket
        must resolve through the exactly-once retry (re-prefill)."""
        from . import chaos as _chaos

        body = wire.pack_trace(trace) + spec["frame"]
        t0 = time.perf_counter()
        ch = _chaos.get_chaos()
        tear = ch.torn_migration_send if ch is not None else None
        fut = self._dx.begin(_F_MIGRATE, body, _parse_submit_response,
                             tear=tear)
        if trace is not None:
            profiler.add_trace_event(
                "wire.send", t0, time.perf_counter() - t0,
                trace.child(), cat="fleet",
                args={"rid": self.rid, "bytes": len(body),
                      "op": "migrate"})
        return fut

    def set_role(self, role: str) -> Dict:
        return self._ctrl({"op": "role", "role": role})

    def publish_adapter(self, name, a, b, alpha=None) -> int:
        """Hot LoRA publish over the wire: the (A, B) slabs ride the
        signed page-frame encoding (no drain on the replica — see
        :meth:`ReplicaHarness.publish_adapter`).  Returns the slot."""
        meta = {"name": str(name),
                "alpha": None if alpha is None else float(alpha)}
        body = wire.pack_trace(None) + wire.pack_page_frame(
            self._secret, meta, [np.asarray(a), np.asarray(b)])

        def parse(status, payload):
            if status != _ST_OK:
                return MXNetError(
                    bytes(payload).decode(errors="replace"))
            return json.loads(bytes(payload).decode())

        return int(self._dx.begin(_F_ADAPTER, body, parse)
                   .result(300.0)["slot"])

    def retire_adapter(self, name) -> bool:
        return bool(self._ctrl({"op": "retire_adapter",
                                "name": str(name)})["freed"])

    def _ctrl(self, obj: Dict, timeout: float = 120.0) -> Dict:
        def parse(status, payload):
            if status != _ST_OK:
                return MXNetError(bytes(payload).decode(errors="replace"))
            return json.loads(bytes(payload).decode())

        body = wire.pack_trace(None) \
            + wire.pack_signed_json(self._secret, obj)
        return self._dx.begin(_F_CTRL, body, parse).result(timeout)

    def inflight(self) -> int:
        return int(self._ctrl({"op": "inflight"})["inflight"])

    def drain(self, timeout: float = 30.0) -> int:
        return int(self._ctrl({"op": "drain", "timeout": timeout},
                              timeout=timeout + 30.0)["inflight"])

    def resume(self):
        self._ctrl({"op": "resume"})

    def swap(self, ckpt_dir: str, drain_timeout: float = 60.0) -> Dict:
        # warmup recompiles every bucket — allow it generous wall time
        return self._ctrl({"op": "swap", "ckpt_dir": ckpt_dir,
                           "drain_timeout": drain_timeout},
                          timeout=drain_timeout + 1800.0)

    def stats(self) -> Dict:
        return self._ctrl({"op": "stats"})

    def stop(self):
        try:
            self._ctrl({"op": "stop"}, timeout=10.0)
        except Exception:  # noqa: BLE001 — best effort
            pass

    def close(self):
        self._dx.close()


# ---------------------------------------------------------------------------
# replica process launch
# ---------------------------------------------------------------------------


def write_secret(fleet_dir: str, secret: bytes) -> str:
    """Persist the wire secret for replica processes (0600 — the
    membership-ledger convention for key material)."""
    os.makedirs(fleet_dir, exist_ok=True)
    path = os.path.join(fleet_dir, "secret")
    from .checkpoint import atomic_write_bytes

    atomic_write_bytes(path, secret.hex().encode())
    try:
        os.chmod(path, 0o600)
    except OSError:
        pass
    return path


def read_secret(fleet_dir: str) -> bytes:
    try:
        with open(os.path.join(fleet_dir, "secret")) as f:
            return bytes.fromhex(f.read().strip())
    except (OSError, ValueError):
        return b""


def read_endpoint(fleet_dir: str, rid: int,
                  timeout: float = 120.0) -> Tuple[str, int]:
    """Wait for replica ``rid``'s endpoint file (written once its
    server is listening) → (host, port)."""
    path = os.path.join(fleet_dir, f"ep_{rid}")
    deadline = time.monotonic() + timeout
    while True:
        try:
            with open(path) as f:
                host, port = f.read().strip().rsplit(":", 1)
                return host, int(port)
        except (OSError, ValueError):
            if time.monotonic() > deadline:
                raise MXNetError(
                    f"replica {rid} never announced an endpoint in "
                    f"{fleet_dir} within {timeout:.0f}s")
            time.sleep(0.1)


def spawn_replica(rid: int, fleet_dir: str, builder: str,
                  builder_kwargs: Optional[Dict] = None,
                  env: Optional[Dict[str, str]] = None,
                  devices: Optional[Sequence[int]] = None
                  ) -> subprocess.Popen:
    """Start one replica process: ``python -m mxnet_tpu.fleet`` imports
    ``builder`` ("pkg.module:function"), calls it with
    ``builder_kwargs`` to construct the engine, wraps it in a
    ReplicaHarness, and serves until stopped (or until its parent
    dies — replicas watch getppid, the io_pool orphan rule).

    ``devices``: device ordinals this replica's engine meshes over —
    exported as ``MXNET_SERVING_DEVICES`` so a model-parallel replica
    (MXNET_SERVING_TP / MXNET_SERVING_PP > 1) binds its tp x pp slice
    of the host's chips while its siblings bind theirs.

    Each replica is a JAX process of its own, and a TPU chip belongs
    to one process at a time: on a TPU host ``env`` must give the
    replica its chip(s) (``TPU_VISIBLE_CHIPS``; the ordinals in
    ``devices`` then count within what it sees), and the parent — the
    router — must never have created a jax backend.  Otherwise this
    refuses (``config.refuse_shared_chip``) instead of starting a
    child that hangs."""
    spec = {"rid": int(rid), "fleet_dir": fleet_dir, "builder": builder,
            "kwargs": builder_kwargs or {}, "parent": os.getpid()}
    child_env = dict(os.environ)
    child_env.update(env or {})
    if devices is not None:
        child_env["MXNET_SERVING_DEVICES"] = \
            ",".join(str(int(d)) for d in devices)
    from .config import refuse_shared_chip

    refuse_shared_chip(child_env, f"fleet.spawn_replica(rid={rid})")
    return subprocess.Popen(
        [sys.executable, "-m", "mxnet_tpu.fleet", json.dumps(spec)],
        env=child_env)


def _replica_main(spec: Dict) -> int:
    from .serving import ReplicaHarness
    from .checkpoint import atomic_write_bytes

    rid = int(spec["rid"])
    fleet_dir = spec["fleet_dir"]
    # flight recorder: point the mmap ring file at the shared fleet
    # dir (unless the operator chose one) so a kill -9'd replica's
    # last-N-seconds record survives WHERE THE DRILL LOOKS
    if not os.environ.get("MXNET_FLIGHT_RECORDER_DIR"):
        profiler.init_flight_recorder(fleet_dir)
    mod_name, _, fn_name = spec["builder"].partition(":")
    import importlib

    if mod_name.endswith(".py"):
        # a script builder (tools/bench_fleet.py) — load by file path
        import importlib.util

        mspec = importlib.util.spec_from_file_location(
            "_fleet_builder", mod_name)
        module = importlib.util.module_from_spec(mspec)
        mspec.loader.exec_module(module)
    else:
        module = importlib.import_module(mod_name)
    builder = getattr(module, fn_name)
    engine = builder(**spec.get("kwargs", {}))
    harness = engine if isinstance(engine, ReplicaHarness) \
        else ReplicaHarness(engine)
    server = ReplicaServer(harness, rid, fleet_dir=fleet_dir,
                           secret=read_secret(fleet_dir))
    atomic_write_bytes(os.path.join(fleet_dir, f"ep_{rid}"),
                       f"127.0.0.1:{server.port}".encode())
    # ops endpoint: replicas always bind an EPHEMERAL port (N replicas
    # on one host can't share MXNET_METRICS_PORT) and publish it as
    # mz_<rid> — tools/fleet_top.py polls these /statusz endpoints
    try:
        mz = profiler.start_metrics_server(port=0)
        profiler.register_statusz(
            "replica", lambda: {"rid": rid, "pid": os.getpid(),
                                "port": server.port})
        atomic_write_bytes(os.path.join(fleet_dir, f"mz_{rid}"),
                           f"127.0.0.1:{mz.port}".encode())
    except Exception:  # noqa: BLE001 — ops surface must not kill serving
        pass
    _log.warning("[fleet] replica %d serving on :%d (pid %d)",
                 rid, server.port, os.getpid())
    parent = int(spec.get("parent", 0))
    while not server.wait_closed(timeout=1.0):
        if parent and os.getppid() != parent:
            _log.warning("[fleet] replica %d: parent died; exiting", rid)
            server.close()
            return 0
    return 0


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------


class _Ticket:
    """One client request's life in the router: assigned → (retried)* →
    delivered exactly once."""

    __slots__ = ("tid", "spec", "deadline", "units", "attempts",
                 "rid", "t_submit", "t_dispatch", "future", "delivered",
                 "queued", "trace", "t_enqueue", "tp_submit",
                 "tp_dispatch", "trace_owned", "slo_class", "canary",
                 "phase", "spec0", "failures", "prefill_rid",
                 "tp_prefill_done", "mig_pages", "tenant")

    def __init__(self, tid, spec, deadline, units, future, trace=None,
                 slo_class="interactive", canary=False, tenant=None):
        self.tid = tid
        self.spec = spec
        self.deadline = deadline      # absolute monotonic, or None
        self.units = units            # work units (samples / new tokens)
        self.attempts = 0
        self.rid = None               # replica currently owning it
        self.t_submit = time.monotonic()
        self.t_dispatch = 0.0
        self.future = future          # resolves toward the client
        self.delivered = False        # retired: exactly-once latch
        self.queued = True            # sitting in Router._pending
        self.trace = trace            # TraceContext | None
        # perf_counter twins of the monotonic stamps — span timestamps
        # share the clock every other span in the process uses
        self.tp_submit = time.perf_counter()
        self.t_enqueue = self.tp_submit  # (re)joined the queue
        self.tp_dispatch = 0.0
        self.trace_owned = False  # router created the root span
        self.slo_class = slo_class  # validated at _accept()
        self.canary = canary        # excluded from request counters
        self.tenant = tenant        # quota bucket / fairness key
        # disaggregated serving: 0 = classic end-to-end dispatch,
        # 1 = prefill-export in flight, 2 = page migration / decode
        # continuation in flight.  ANY retry resets to 1 with spec0
        # (decode death re-prefills; prefill death retries prefill).
        self.phase = 0
        self.spec0 = None             # pristine spec for phase resets
        self.failures = 0             # replica failures (retry budget)
        self.prefill_rid = None       # who ran phase 1 (migration edge)
        self.tp_prefill_done = 0.0    # phase-1 completion (disagg TTFT)
        self.mig_pages = 0            # pages riding the phase-2 frame


class _ReplicaState:
    __slots__ = ("handle", "outstanding", "draining", "dead", "swaps",
                 "role", "free_blocks", "kv_block", "cache_util",
                 "role_flips")

    def __init__(self, handle):
        self.handle = handle
        self.outstanding: Dict[int, _Ticket] = {}
        self.draining = False
        self.dead = False
        self.swaps = 0
        self.role = "mixed"           # disagg role (roles off = mixed)
        # decode-capacity ledger: refreshed from handle.stats() by the
        # monitor loop, decremented optimistically at phase-2 dispatch.
        # None = never measured → admit and measure (the PR-1 rule).
        self.free_blocks: Optional[int] = None
        self.kv_block: Optional[int] = None
        self.cache_util: Optional[float] = None
        self.role_flips = 0


class Router:
    """Spread requests over N replicas; survive replica death; shed by
    deadline; roll weight swaps with zero dropped requests.

    Parameters
    ----------
    replicas : list
        Replica handles (:class:`ReplicaClient` or any in-process
        object with the same surface: ``rid``, ``submit(spec) ->
        Future``, ``inflight()``, ``drain()``, ``resume()``,
        ``swap()``, ``stats()``, ``close()``).
    fleet_dir : str, optional
        The shared heartbeat directory replicas write ``hb_<rid>``
        into; enables the staleness scan.  Without it only transport
        failures convict a replica.
    secret : bytes
        HMAC key for structured control payloads (and the client
        wire's server, when :meth:`serve` is called).
    retry_budget : int
        Re-dispatches a ticket survives before its client sees the
        failure (env ``MXNET_FLEET_RETRY_BUDGET``).
    default_deadline_ms : float
        Deadline applied to requests that carry none; 0 = unbounded
        (env ``MXNET_FLEET_SHED_DEADLINE_MS``).
    replica_depth : int
        Max tickets outstanding on one replica; beyond it requests
        queue in the router (where they can still be shed/retried).
    max_pending : int
        Router queue bound; above it the pending queue sheds
        oldest-deadline-first.
    dead_timeout : float
        Heartbeat staleness threshold (``MXNET_DEAD_RANK_TIMEOUT``).
    """

    def __init__(self, replicas, fleet_dir: Optional[str] = None,
                 secret: bytes = b"", retry_budget: Optional[int] = None,
                 default_deadline_ms: Optional[float] = None,
                 replica_depth: int = 8, max_pending: int = 1024,
                 dead_timeout: Optional[float] = None,
                 roles: Optional[Sequence[str]] = None,
                 autoscale: Optional[bool] = None,
                 tenant_quota=None):
        if not replicas:
            raise MXNetError("Router needs at least one replica")
        self._fleet_dir = fleet_dir
        self._secret = secret
        self._retry_budget = int(
            fleet_env("MXNET_FLEET_RETRY_BUDGET")
            if retry_budget is None else retry_budget)
        dl = (fleet_env("MXNET_FLEET_SHED_DEADLINE_MS")
              if default_deadline_ms is None else default_deadline_ms)
        self._default_deadline_s = float(dl) / 1e3 if dl else None
        self._replica_depth = int(replica_depth)
        self._max_pending = int(max_pending)
        self._dead_timeout = (dead_rank_timeout() if dead_timeout is None
                              else float(dead_timeout))
        self._swap_drain_timeout = float(
            fleet_env("MXNET_FLEET_SWAP_DRAIN_TIMEOUT"))

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._replicas: Dict[int, _ReplicaState] = {}
        for h in replicas:
            rid = int(h.rid)
            if rid in self._replicas:
                raise MXNetError(f"duplicate replica id {rid}")
            self._replicas[rid] = _ReplicaState(h)
            cb = getattr(h, "set_on_death", None)
            if cb is not None:
                cb(lambda exc, _rid=rid: self._replica_failed(_rid, exc))
        # disaggregated prefill/decode roles: kwarg wins, else the
        # MXNET_FLEET_ROLES split (by rid order), else roles stay off
        role_list = list(roles) if roles is not None else roles_env()
        self._roles_on = role_list is not None
        if role_list is not None:
            rids = sorted(self._replicas)
            if len(role_list) != len(rids):
                raise MXNetError(
                    f"{len(role_list)} role(s) for {len(rids)} "
                    f"replica(s) — the role split must name every "
                    f"replica (rid order: {rids})")
            for role in role_list:
                if role not in REPLICA_ROLES:
                    raise MXNetError(
                        f"replica role {role!r} must be one of "
                        f"{REPLICA_ROLES}")
            if ("prefill" in role_list) != ("decode" in role_list):
                raise MXNetError(
                    "a disaggregated fleet needs BOTH a prefill and a "
                    "decode role (or neither)")
            for rid, role in zip(rids, role_list):
                state = self._replicas[rid]
                state.role = role
                if role != "mixed":
                    setter = getattr(state.handle, "set_role", None)
                    if setter is None:
                        raise MXNetError(
                            f"replica {rid} handle has no set_role() — "
                            "it cannot take a disaggregated role")
                    setter(role)
        self._pending: List[_Ticket] = []
        self._next_tid = 0
        self._alive = True
        import collections as _collections

        self._shed_times = _collections.deque(maxlen=_SHED_BURST_COUNT)
        self._last_shed_dump = 0.0
        # multi-tenancy: accept-side token quotas (kwarg wins, else
        # MXNET_TENANT_QUOTA_TOKENS/_REFILL) + per-tenant fairness
        # counters the /statusz tenants section renders
        self._quota = tenant_quota if tenant_quota is not None \
            else _adapters.quota_from_env()
        self._tenants: Dict[str, Dict[str, float]] = {}
        self._adapters: set = set()  # names published via this router
        self._swap_lock = threading.Lock()  # one rolling swap at a time
        self._weights_step = -1

        # PR-1-style learned cost model: (kind, bucket) -> EMA ms of
        # dispatch->delivery wall for one request in that bucket.  The
        # shed verdict leans on it: no measurement yet = nothing is
        # provable = admit (measure instead of assume).
        self._cost: Dict[Tuple[str, int], float] = {}
        self._metrics = profiler.MetricsRegistry()
        # assigned BEFORE the worker threads exist: both loops book
        # delivery/shed outcomes into the process-wide tracker
        self._slo = _slo.get_tracker()

        self._server = None
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name="mxnet_tpu-fleet-dispatch")
        self._dispatcher.start()
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True,
            name="mxnet_tpu-fleet-monitor")
        self._monitor.start()
        # role autoscaler: periodically re-evaluate the prefill/decode
        # split from live telemetry (queue depths, cache_util ledger,
        # per-kind cost EMAs) — MXNET_FLEET_AUTOSCALE gates the thread;
        # autoscale_once() stays callable for deterministic drills
        self._autoscale_on = bool(
            int(fleet_env("MXNET_FLEET_AUTOSCALE"))
            if autoscale is None else autoscale)
        self._autoscale_interval = float(
            fleet_env("MXNET_FLEET_AUTOSCALE_INTERVAL"))
        if self._autoscale_on and self._roles_on:
            threading.Thread(
                target=self._autoscale_loop, daemon=True,
                name="mxnet_tpu-fleet-autoscale").start()
        self._set_alive_gauge()
        # ops surface: /statusz grows a router section; the HTTP
        # endpoint itself is MXNET_METRICS_PORT-gated
        profiler.maybe_start_metrics_server()
        profiler.register_statusz("router", self.stats)
        # optional canary prober: keeps availability and latency
        # observable at zero traffic (MXNET_CANARY_INTERVAL=0 leaves
        # it off).  The probe rides the FULL routed path — accept →
        # dispatch → replica → deliver — as a canary ticket.
        self._canary = None
        interval = _slo.canary_interval_s()
        if interval > 0:
            def _probe(trace):
                self.generate(
                    _slo.canary_prompt(4),
                    max_new_tokens=_slo.canary_tokens(),
                    trace=trace, canary=True).result(timeout=60.0)

            self._canary = _slo.CanaryProber(
                _probe, interval, tracker=self._slo, name="router")

    # -- metrics --------------------------------------------------------
    def _count(self, name, value=1.0):
        self._metrics.inc(name, value)
        profiler.inc_counter(f"fleet.{name}", value)

    def _tenant_count(self, tenant, name, value=1):
        with self._lock:
            d = self._tenants.setdefault(tenant, {})
            d[name] = d.get(name, 0) + value

    def _set_alive_gauge(self):
        profiler.set_gauge(
            "fleet.replicas_alive",
            sum(not s.dead for s in self._replicas.values()))

    # -- client surface -------------------------------------------------
    def submit(self, inputs, deadline_ms: Optional[float] = None,
               trace=None) -> Future:
        """Route one inference request; the Future resolves to the list
        of output arrays (or raises :class:`ShedError` /
        the replica's error).  ``trace``: the caller's
        :class:`profiler.TraceContext` (the served wire passes the
        client's through); None = a sampled root context."""
        return self._accept({"kind": "infer", "inputs": dict(inputs)},
                            deadline_ms,
                            units=self._infer_units(inputs),
                            trace=trace)

    def generate(self, prompt, max_new_tokens=32, temperature=None,
                 eos_id=None, deadline_ms: Optional[float] = None,
                 seed: Optional[int] = None, trace=None,
                 slo_class: str = "interactive",
                 canary: bool = False, tenant=None,
                 adapter=None) -> Future:
        """Route one generation; the Future resolves to the np.int32
        generated tokens.  ``slo_class`` keys the burn-rate windows the
        delivery outcome lands in; ``canary=True`` marks a synthetic
        probe (full routed path, excluded from ``fleet.requests``).
        ``tenant`` names the quota/fairness bucket (sheds typed
        ``tenant_quota`` when its token budget runs dry); ``adapter``
        names a published LoRA adapter the replicas apply to this
        stream."""
        spec = {"kind": "decode",
                "prompt": np.asarray(prompt, dtype=np.int32),
                "max_new": int(max_new_tokens), "temperature": temperature,
                "eos": eos_id, "seed": 0, "slo_class": slo_class,
                "tenant": None if tenant is None else str(tenant),
                "adapter": None if adapter is None else str(adapter)}
        return self._accept(spec, deadline_ms, units=int(max_new_tokens),
                            seed=seed, trace=trace, slo_class=slo_class,
                            canary=canary, tenant=spec["tenant"])

    @staticmethod
    def _infer_units(inputs) -> int:
        for v in inputs.values():
            shape = np.shape(v)
            return max(1, int(shape[0]) if len(shape) else 1)
        return 1

    def _accept(self, spec, deadline_ms, units, seed=None,
                trace=None, slo_class="interactive",
                canary=False, tenant=None) -> Future:
        _slo.check_class(slo_class)
        if self._quota is not None and tenant is not None \
                and not canary:
            # accept-side quota: shed BEFORE the ticket takes queue
            # space — typed, so clients and dashboards can tell a
            # budget problem from an overload problem
            tokens = int(units)
            if spec["kind"] == "decode":
                tokens += int(np.asarray(spec["prompt"]).size)
            try:
                self._quota.charge(tenant, tokens)
            except _adapters.QuotaExceededError as exc:
                self._count("shed")
                self._count("shed_tenant_quota")
                self._tenant_count(tenant, "shed")
                self._note_shed()
                raise ShedError(
                    f"request shed (tenant_quota): {exc}",
                    reason="tenant_quota") from None
        fut: Future = Future()
        with self._cond:
            if not self._alive:
                raise MXNetError("Router is closed")
            tid = self._next_tid
            self._next_tid += 1
            if spec["kind"] == "decode":
                # the deterministic retry seed: stable across replicas
                # AND across re-dispatches of this ticket
                spec["seed"] = int(seed) if seed is not None \
                    else tid + 1
            if deadline_ms is None:
                deadline = (None if self._default_deadline_s is None
                            else time.monotonic()
                            + self._default_deadline_s)
            else:
                deadline = time.monotonic() + float(deadline_ms) / 1e3
            owned = False
            if trace is None:
                # direct (in-process) callers get a sampled root; the
                # tid key keeps the verdict stable across retries
                trace = profiler.make_trace(key=tid)
                owned = trace is not None
            t = _Ticket(tid, spec, deadline, max(1, units), fut,
                        trace=trace, slo_class=slo_class, canary=canary,
                        tenant=tenant)
            t.trace_owned = owned
            self._pending.append(t)
            profiler.set_gauge("fleet.pending", len(self._pending))
            self._cond.notify_all()
        if not canary:  # probes keep request counters honest
            self._count("requests")
            if tenant is not None:
                self._tenant_count(tenant, "requests")
        return fut

    # -- cost model -----------------------------------------------------
    @staticmethod
    def _bucket_of(units: int) -> int:
        b = 1
        while b < units:
            b <<= 1
        return b

    def _est_ms(self, t: _Ticket) -> Optional[float]:
        return self._cost.get((t.spec["kind"], self._bucket_of(t.units)))

    def _observe_cost(self, t: _Ticket, ms: float):
        key = (t.spec["kind"], self._bucket_of(t.units))
        old = self._cost.get(key)
        self._cost[key] = ms if old is None else 0.5 * old + 0.5 * ms

    def _predicted_wait_ms(self, state: _ReplicaState,
                           t: _Ticket) -> Optional[float]:
        """Projected dispatch→done wall on this replica: the measured
        cost of everything it already owns plus this ticket.  None =
        no measurement for some bucket → nothing provable."""
        total = 0.0
        for o in state.outstanding.values():
            est = self._est_ms(o)
            if est is None:
                return None
            total += est
        est = self._est_ms(t)
        if est is None:
            return None
        return total + est

    # -- dispatch -------------------------------------------------------
    def _disagg_live(self) -> bool:
        """Both specialized roles present among live, non-draining
        replicas (lock held).  When one side is gone — died, or all
        flipped away — the fleet degrades to classic mixed routing
        instead of wedging."""
        if not self._roles_on:
            return False
        has_p = has_d = False
        for s in self._replicas.values():
            if s.dead or s.draining:
                continue
            has_p = has_p or s.role == "prefill"
            has_d = has_d or s.role in ("decode", "mixed")
        return has_p and has_d

    def _decode_room(self, need_blocks: int) -> bool:
        """Role-aware admission (lock held): does SOME decode-capable
        replica have room for this stream's eventual KV pages?  An
        unmeasured ledger admits (measure instead of assume)."""
        for s in self._replicas.values():
            if s.dead or s.draining or s.role == "prefill":
                continue
            if s.free_blocks is None or s.free_blocks >= need_blocks:
                return True
        return False

    def _need_blocks(self, t: _Ticket, kv_block: Optional[int]) -> int:
        """Worst-case pages a decode ticket will hold: prompt+max_new
        over the page grid (phase-2 tickets carry the exact count)."""
        if t.phase == 2:
            return t.mig_pages
        if not kv_block:
            return 0  # page size never measured → gate on nothing
        spec = t.spec0 if t.spec0 is not None else t.spec
        tokens = int(np.asarray(spec["prompt"]).size) \
            + int(spec["max_new"])
        return -(-tokens // int(kv_block))

    def _eligible(self, t: _Ticket):
        """(best replica or None, provably_unmeetable) under the lock.

        'Provably unmeetable' requires EVERY live replica's measured
        projected wait to exceed the remaining deadline — a replica
        that is merely at depth (can't take the ticket NOW but could
        meet the deadline once a slot frees) keeps the request
        admitted, and any unmeasured bucket makes nothing provable
        (the PR-1 rule: explore/measure instead of assume).

        Disaggregated routing (both roles live): fresh decode work
        lands on prefill-role or mixed replicas, phase-2 migrations
        land on decode-role or mixed replicas WITH free pool pages for
        the spliced stream, and a prefill-role replica only takes a
        fresh stream when some decode-capable replica has room for its
        eventual pages — admission keys on free decode blocks on the
        TARGET role, not just queue depth."""
        best, best_wait = None, None
        provable = t.deadline is not None
        meetable = False  # some live replica could finish in time
        remaining_ms = (None if t.deadline is None
                        else (t.deadline - time.monotonic()) * 1e3)
        # routing estimate for unmeasured buckets: the mean of the
        # measured ones (commensurable with real waits — a raw
        # outstanding COUNT would always undercut millisecond keys and
        # pile work onto whichever replica holds unmeasured requests)
        fallback = (sum(self._cost.values()) / len(self._cost)
                    if self._cost else 1.0)
        disagg = t.spec["kind"] != "infer" and self._disagg_live()
        for state in self._replicas.values():
            if state.dead or state.draining:
                continue
            if disagg:
                if t.phase == 2:
                    if state.role == "prefill":
                        continue  # pages splice into DECODE pools
                    if state.free_blocks is not None \
                            and state.free_blocks < t.mig_pages:
                        continue  # no room to splice (yet)
                else:
                    if state.role == "decode":
                        continue  # fresh prefills stay off decoders
                    if state.role == "prefill" and not self._decode_room(
                            self._need_blocks(t, state.kv_block)):
                        continue  # prefilling now would strand the KV
            wait = self._predicted_wait_ms(state, t)
            if wait is None:
                provable = False  # unmeasured bucket: admit, measure
                meetable = True
                wait_key = fallback * (len(state.outstanding) + 1)
            else:
                if remaining_ms is not None and wait > remaining_ms:
                    continue  # this replica provably misses
                meetable = True
                wait_key = wait
            if len(state.outstanding) >= self._replica_depth:
                continue  # meetable, just not dispatchable yet
            if best is None or wait_key < best_wait:
                best, best_wait = state, wait_key
        return best, (best is None and provable and not meetable
                      and self._any_live_not_draining())

    def _any_live_not_draining(self) -> bool:
        return any(not s.dead and not s.draining
                   for s in self._replicas.values())

    def _dispatch_loop(self):
        while True:
            todo = []
            with self._cond:
                while self._alive and not self._pending:
                    self._cond.wait(timeout=0.2)
                if not self._alive:
                    return
                now = time.monotonic()
                # 1) shed what already missed: serving it late only
                #    poisons p99 and steals capacity from the living
                keep = []
                for t in self._pending:
                    if t.delivered:  # zombie answered while queued
                        t.queued = False
                        continue
                    if t.deadline is not None and now > t.deadline:
                        self._shed_locked(
                            t, "expired",
                            f"deadline passed while queued "
                            f"({(now - t.t_submit) * 1e3:.0f} ms in "
                            f"queue)")
                    else:
                        keep.append(t)
                self._pending = keep
                # 2) overload: shed oldest-deadline-first down to the
                #    bound (no-deadline requests shed last, oldest
                #    submit first among them)
                while len(self._pending) > self._max_pending:
                    victim = min(
                        self._pending,
                        key=lambda t: (t.deadline
                                       if t.deadline is not None
                                       else float("inf"), t.t_submit))
                    self._pending.remove(victim)
                    self._shed_locked(
                        victim, "overload",
                        f"router queue over {self._max_pending}; "
                        "oldest-deadline-first shed")
                # 3) assign FIFO within an SLO tier: the first
                #    interactive ticket jumps the batch queue
                #    (admission-level preemption); a head that no
                #    replica can take means the fleet is at depth —
                #    hold the line
                while self._pending:
                    pick = 0
                    for i, cand in enumerate(self._pending):
                        if cand.slo_class == "interactive":
                            pick = i
                            break
                    t = self._pending[pick]
                    state, unmeetable = self._eligible(t)
                    if state is None:
                        if unmeetable:
                            self._pending.pop(pick)
                            t.queued = False
                            self._shed_locked(
                                t, "deadline",
                                "no replica can finish inside the "
                                f"deadline (remaining "
                                f"{(t.deadline - now) * 1e3:.0f} ms, "
                                "per-bucket cost model)")
                            continue
                        break
                    self._pending.pop(pick)
                    t.queued = False
                    t.rid = state.handle.rid
                    t.attempts += 1
                    t.t_dispatch = time.monotonic()
                    now_p = t.tp_dispatch = time.perf_counter()
                    if t.spec["kind"] == "decode":
                        # phase is decided by the TARGET's role: a
                        # prefill-role replica runs phase 1 (export
                        # after TTFT); a mixed replica runs the classic
                        # end-to-end decode even on a re-dispatch
                        if state.role == "prefill":
                            if t.spec0 is None:
                                t.spec0 = dict(t.spec)
                            t.phase = 1
                            t.spec = dict(t.spec0)
                            t.spec["phase"] = 1
                        elif t.spec0 is not None:
                            t.phase = 0
                            t.spec = dict(t.spec0)
                    elif t.phase == 2:
                        # page splice: burn the target's block ledger
                        # optimistically (the monitor re-measures) and
                        # book the migration window — export + handoff
                        # queue — the instant the pages leave limbo
                        if state.free_blocks is not None:
                            state.free_blocks = max(
                                0, state.free_blocks - t.mig_pages)
                        mig_ms = (now_p - t.tp_prefill_done) * 1e3 \
                            + float(t.spec.get("meta", {})
                                    .get("export_ms", 0.0))
                        self._metrics.observe("migration_ms", mig_ms)
                        profiler.observe("fleet.migration_ms", mig_ms)
                        self._count("migration_ms_total", mig_ms)
                    wait_ms = (now_p - t.t_enqueue) * 1e3
                    self._metrics.observe("queue_wait_ms", wait_ms)
                    profiler.observe("fleet.queue_wait_ms", wait_ms)
                    if t.attempts == 1:
                        # admission latency: submit → first dispatch
                        # (eligibility + depth gating, incl. queue)
                        adm = (now_p - t.tp_submit) * 1e3
                        self._metrics.observe("admission_ms", adm)
                        profiler.observe("fleet.admission_ms", adm)
                    if t.trace is not None:
                        profiler.add_trace_event(
                            "router.queue", t.t_enqueue,
                            now_p - t.t_enqueue, t.trace.child(),
                            cat="fleet",
                            args={"tid": t.tid, "attempt": t.attempts,
                                  "rid": t.rid})
                    state.outstanding[t.tid] = t
                    profiler.set_gauge(
                        f"fleet.queue_depth.r{t.rid}",
                        len(state.outstanding))
                    todo.append((t, state.handle, t.attempts, t.phase))
                profiler.set_gauge("fleet.pending", len(self._pending))
                if not todo and self._pending:
                    # head can't be placed (fleet at depth / draining):
                    # wait for a completion to free a slot instead of
                    # spinning the shed/assign scan at 100% CPU
                    self._cond.wait(timeout=0.05)
            for t, handle, attempt, phase in todo:
                # the replica sees the ticket's trace context as its
                # parent ("trace" rides the spec to ReplicaClient,
                # which ships it as the wire's optional field;
                # in-process fakes just ignore the key)
                t.spec["trace"] = t.trace
                try:
                    rfut = handle.submit(t.spec)
                except BaseException as exc:  # noqa: BLE001
                    self._replica_failed(handle.rid, exc)
                    continue
                rfut.add_done_callback(
                    lambda f, _t=t, _a=attempt, _r=handle.rid, _p=phase:
                    self._on_done(_t, f, _a, _r, _p))

    def _shed_locked(self, t: _Ticket, reason: str, detail: str):
        t.delivered = True
        t.queued = False
        self._count("shed")
        self._count(f"shed_{reason}")
        if t.tenant is not None:
            # caller holds the router lock; bump inline rather than
            # through _tenant_count (which would re-acquire it)
            d = self._tenants.setdefault(t.tenant, {})
            d["shed"] = d.get("shed", 0) + 1
        if not t.canary:  # a shed request spent availability budget
            self._slo.observe_avail(t.slo_class, False)
        if t.trace is not None:
            profiler.trace_point(
                "router.shed", t.trace.child(), cat="fleet",
                args={"tid": t.tid, "reason": reason})
        self._note_shed()
        exc = ShedError(f"request shed ({reason}): {detail}",
                        reason=reason)
        if t.future.set_running_or_notify_cancel():
            t.future.set_exception(exc)

    def _note_shed(self):
        """Shed-burst detector: a storm of rejections is exactly the
        moment to capture what the router was doing — one flight-
        recorder dump per burst window.  Callers hold the router
        condition lock, so only DETECT here; the dump (ring
        serialization + file write) runs on a throwaway daemon thread
        — blocking every submitter at peak overload would deepen the
        very storm being recorded."""
        now = time.monotonic()
        self._shed_times.append(now)
        if (len(self._shed_times) == self._shed_times.maxlen
                and now - self._shed_times[0] <= _SHED_BURST_WINDOW_S
                and now - self._last_shed_dump >= 2.0):
            self._last_shed_dump = now
            n = len(self._shed_times)
            threading.Thread(
                target=profiler.dump_flight_record,
                args=("shed_burst",),
                kwargs={"extra": {"sheds_in_window": n,
                                  "window_s": _SHED_BURST_WINDOW_S}},
                daemon=True,
                name="mxnet_tpu-fleet-shed-dump").start()

    def _requeue_retry_locked(self, t: _Ticket, rid_from, why: str):
        """Front-of-queue requeue of a retried ticket; books the retry
        histogram and the ``router.retry`` span — whose bounds ARE the
        conviction window (failed dispatch → requeue), so a stitched
        trace shows the dead replica's window explicitly."""
        now_p = time.perf_counter()
        t.t_enqueue = now_p
        self._pending.insert(0, t)  # oldest first
        self._count("retries")
        if t.tp_dispatch:
            retry_ms = (now_p - t.tp_dispatch) * 1e3
            self._metrics.observe("retry_ms", retry_ms)
            profiler.observe("fleet.retry_ms", retry_ms)
            if t.trace is not None:
                profiler.add_trace_event(
                    "router.retry", t.tp_dispatch,
                    now_p - t.tp_dispatch, t.trace.child(),
                    cat="fleet",
                    args={"tid": t.tid, "attempt": t.attempts,
                          "from_rid": rid_from,
                          "error": str(why)[:200]})

    # -- completion -----------------------------------------------------
    def _reset_phase_locked(self, t: _Ticket):
        """ANY retry of a disagg ticket restarts from phase 1 with the
        pristine spec: a dead decode replica's spliced pages are gone
        (re-prefill — the same recompute path preemption uses) and a
        dead prefill replica's frame never materialized."""
        if t.spec0 is not None:
            if t.phase == 2:
                self._count("re_prefills")
            t.phase = 0  # the next dispatch's target role re-decides
            t.spec = dict(t.spec0)
            t.mig_pages = 0
            t.tp_prefill_done = 0.0
            t.prefill_rid = None

    def _on_done(self, t: _Ticket, rfut: Future, attempt: int,
                 rid_disp: int, phase_disp: int = 0):
        """A replica's future resolved for dispatch #``attempt`` of
        this ticket.  Exactly-once lives here: the ``delivered`` latch
        retires the ticket on FIRST delivery; a late/stale completion
        (the ticket was already retried elsewhere, or already answered)
        is dropped, never double-delivered and never double-retried.

        ``phase_disp`` is the phase THIS dispatch ran: a phase-1
        success is not a delivery — it converts the ticket into a
        phase-2 page migration and front-requeues it (the stream is
        past its prefill; the splice must not wait behind fresh
        admissions)."""
        exc = rfut.exception()
        retry = False
        override = None
        with self._cond:
            current = (t.attempts == attempt)
            if current:
                state = self._replicas.get(rid_disp)
                if state is not None:
                    state.outstanding.pop(t.tid, None)
                    if not state.dead:
                        profiler.set_gauge(
                            f"fleet.queue_depth.r{rid_disp}",
                            len(state.outstanding))
            if t.delivered:
                # late answer from a dispatch we already gave up on:
                # the ticket is retired — exactly-once means DROP it
                self._count("duplicates")
                self._cond.notify_all()
                return
            if exc is None and phase_disp == 1:
                if not current or t.queued:
                    # a stale page frame (the live attempt re-prefills
                    # or already moved on): splicing it ANYWHERE could
                    # race the live stream — drop it, exactly once
                    self._count("duplicates")
                    self._cond.notify_all()
                    return
                res = rfut.result()
                meta = res["meta"]
                now_p = time.perf_counter()
                t.tp_prefill_done = now_p
                t.prefill_rid = rid_disp
                self._observe_cost(
                    t, (time.monotonic() - t.t_dispatch) * 1e3)
                # disaggregated TTFT: the first token exists the
                # moment prefill completes — the decode tail can no
                # longer move this number
                ttft = (now_p - t.tp_submit) * 1e3
                self._metrics.observe("ttft_ms", ttft)
                profiler.observe("fleet.ttft_ms", ttft)
                if meta.get("done"):
                    # finished at prefill (max_new == 1 / instant
                    # eos): nothing to migrate — deliver directly
                    t.delivered = True
                    override = [np.asarray(res["arrays"][1], np.int32)]
                else:
                    t.phase = 2
                    t.mig_pages = int(meta.get("n_pages", 0))
                    t.spec = {"kind": "migrate", "meta": meta,
                              "frame": res.get("frame"),
                              "arrays": res.get("arrays")}
                    t.queued = True
                    t.t_enqueue = now_p
                    self._pending.insert(0, t)
                    nbytes = int(meta.get("migration_bytes", 0))
                    self._count("migrations")
                    self._count("migration_bytes", nbytes)
                    if t.trace is not None:
                        # the migration edge of the span tree: ties
                        # the prefill replica's migrate_out to the
                        # decode replica's migrate_in across processes
                        profiler.trace_point(
                            "router.migrate", t.trace.child(),
                            cat="fleet",
                            args={"tid": t.tid,
                                  "from_rid": rid_disp,
                                  "pages": t.mig_pages,
                                  "bytes": nbytes})
                    self._cond.notify_all()
                    return
            elif exc is None:
                # even a STALE success delivers (the convicted replica
                # answered after all — first answer wins; the live
                # retry's answer will hit the latch above).  If
                # _replica_failed already requeued the ticket, pull it
                # back out: a delivered ticket left in _pending would
                # be re-dispatched (wasted work) and later shed/close
                # passes would trip on its finished future.
                t.delivered = True
                if t.queued:
                    t.queued = False
                    try:
                        self._pending.remove(t)
                    except ValueError:
                        pass
                if current:
                    self._observe_cost(
                        t, (time.monotonic() - t.t_dispatch) * 1e3)
            elif not current or t.queued:
                # stale failure, or _replica_failed already requeued
                # this ticket: the live dispatch owns the outcome
                self._cond.notify_all()
                return
            elif self._is_replica_failure(exc):
                t.failures += 1
                if t.failures <= self._retry_budget:
                    retry = True
                    t.queued = True
                    self._reset_phase_locked(t)
                    self._requeue_retry_locked(t, rid_disp, str(exc))
                else:
                    t.delivered = True
            else:
                t.delivered = True  # the request itself is bad
            self._cond.notify_all()
        if retry:
            return
        lat_ms = (time.monotonic() - t.t_submit) * 1e3
        self._metrics.observe("latency_ms", lat_ms)
        profiler.observe("fleet.latency_ms", lat_ms)
        if not t.canary:
            # the delivery outcome feeds the availability objective; a
            # canary ticket's outcome is the PROBER's to book (it also
            # sees probe failures this path never reaches)
            self._slo.observe_avail(t.slo_class, exc is None)
        if t.trace is not None:
            now_p = time.perf_counter()
            # the router-residency span (submit → delivery).  When the
            # router MINTED the trace (no wire client upstream) this
            # span IS the root — every queue/retry/replica span nests
            # under it; with a FleetClient upstream it is a child of
            # the client.request root instead.
            profiler.add_trace_event(
                "router.request", t.tp_submit, now_p - t.tp_submit,
                t.trace if t.trace_owned else t.trace.child(),
                cat="fleet",
                args={"tid": t.tid, "attempts": t.attempts,
                      "rid": t.rid, "ok": exc is None})
            profiler.trace_point(
                "router.deliver", t.trace.child(), cat="fleet",
                args={"tid": t.tid, "ok": exc is None})
        if exc is None and t.tp_prefill_done:
            # disagg decode tail: per-token latency AFTER the handoff
            # (the number the prefill/decode isolation bench bounds)
            res_peek = rfut.result() if override is None else override
            toks = res_peek[0] if isinstance(res_peek, (list, tuple)) \
                else res_peek
            n = max(1, int(np.asarray(toks).size) - 1)
            dms = ((time.perf_counter() - t.tp_prefill_done) * 1e3) / n
            self._metrics.observe("decode_ms_per_token", dms)
            profiler.observe("fleet.decode_ms_per_token", dms)
        if t.future.set_running_or_notify_cancel():
            if exc is None:
                self._count("responses")
                res = rfut.result() if override is None else override
                # handle contract: a LIST of output arrays (decode =
                # one token tensor) — unwrap for generate() callers
                if t.spec["kind"] in ("decode", "migrate") \
                        and isinstance(res, (list, tuple)):
                    res = res[0]
                t.future.set_result(res)
            else:
                self._count("failures")
                t.future.set_exception(exc)

    @staticmethod
    def _is_replica_failure(exc: BaseException) -> bool:
        """Failures that indict the REPLICA (retry elsewhere), vs the
        request (fail the client: validation, bad shapes...)."""
        from .serving import EngineClosedError

        if isinstance(exc, (EngineClosedError, ConnectionError)):
            return True
        if isinstance(exc, MXNetError):
            msg = str(exc)
            return any(tok in msg for tok in
                       ("connection", "died", "closed", "reset",
                        "peer", "draining"))
        return isinstance(exc, OSError)

    # -- health ---------------------------------------------------------
    def _monitor_loop(self):
        interval = min(heartbeat_interval(), self._dead_timeout / 4.0)
        while True:
            with self._lock:
                if not self._alive:
                    return
                rids = [r for r, s in self._replicas.items()
                        if not s.dead]
            if self._fleet_dir:
                for rid in stale_ids(self._fleet_dir, rids,
                                     timeout=self._dead_timeout):
                    self._replica_failed(
                        rid, MXNetError("heartbeat went stale"))
            for rid in rids:
                dead = getattr(self._replicas[rid].handle,
                               "transport_dead", None)
                if dead is not None:
                    self._replica_failed(rid, dead)
            if self._roles_on:
                self._refresh_ledger(rids)
            time.sleep(max(0.02, interval))

    def _refresh_ledger(self, rids):
        """Re-measure each replica's decode-capacity ledger (free pool
        blocks / page size / cache_util) from its stats — the signals
        role-aware admission and the autoscaler route on.  Best-effort:
        a replica that cannot answer keeps its last measurement (a
        dying one gets convicted by the passes above, not here)."""
        for rid in rids:
            state = self._replicas.get(rid)
            if state is None or state.dead:
                continue
            try:
                st = state.handle.stats()
            except Exception:  # noqa: BLE001 — measurement only
                continue
            with self._lock:
                if st.get("cache_blocks_free") is not None:
                    state.free_blocks = int(st["cache_blocks_free"])
                if st.get("kv_block"):
                    state.kv_block = int(st["kv_block"])
                if st.get("cache_util") is not None:
                    state.cache_util = float(st["cache_util"])
                role = st.get("role")
                if role in REPLICA_ROLES:
                    state.role = role

    def _replica_failed(self, rid: int, exc: BaseException):
        """Convict one replica: mark dead, re-queue its unretired
        tickets on the survivors (the transparent-retry path)."""
        with self._cond:
            if not self._alive:
                return  # teardown closes sockets; not a conviction
            state = self._replicas.get(rid)
            if state is None or state.dead:
                return
            state.dead = True
            orphans = [t for t in state.outstanding.values()
                       if not t.delivered and not t.queued]
            state.outstanding.clear()
            self._count("replica_deaths")
            _log.warning(
                "[fleet] replica %d convicted dead (%s); retrying %d "
                "in-flight request(s) on the survivors", rid, exc,
                len(orphans))
            for t in orphans:
                t.failures += 1
                if t.failures <= self._retry_budget:
                    t.queued = True
                    self._reset_phase_locked(t)
                    self._requeue_retry_locked(t, rid, exc)
                else:
                    t.delivered = True
                    if t.future.set_running_or_notify_cancel():
                        t.future.set_exception(MXNetError(
                            f"request failed on {t.attempts} replica(s); "
                            f"retry budget {self._retry_budget} "
                            f"exhausted (last: {exc})"))
            self._cond.notify_all()
        profiler.del_gauge(f"fleet.queue_depth.r{rid}")
        self._set_alive_gauge()
        # post-mortem: what the ROUTER saw in the seconds before the
        # conviction (the dead replica's own ring file tells its side)
        profiler.dump_flight_record(
            "replica_conviction",
            extra={"rid": rid, "error": str(exc),
                   "retried": len(orphans)})
        try:
            state.handle.close()
        except Exception:  # noqa: BLE001 — already convicted
            pass

    def alive_replicas(self) -> List[int]:
        with self._lock:
            return sorted(r for r, s in self._replicas.items()
                          if not s.dead)

    # -- rolling weight swap --------------------------------------------
    def swap_weights(self, ckpt_dir: str,
                     drain_timeout: Optional[float] = None) -> Dict:
        """Zero-downtime rolling update: one replica at a time —
        stop routing to it, wait for its in-flight tickets to deliver,
        ``swap`` (drain → load committed+checksum-verified manifest →
        warmup) on the replica, re-admit — while the rest of the fleet
        keeps serving.  No request is dropped: traffic redistributes
        around the draining replica, and a swap failure resumes the
        replica on its OLD weights and aborts the roll (replicas
        already swapped stay swapped — re-run to converge).
        """
        from .checkpoint import load_latest_params

        drain_timeout = (self._swap_drain_timeout
                         if drain_timeout is None else float(drain_timeout))
        # verify ONCE router-side before touching any replica: a bad
        # checkpoint must not take even one replica out of rotation
        _params, step, path = load_latest_params(ckpt_dir)
        del _params
        with self._swap_lock:
            t0 = time.monotonic()
            reports: Dict[int, Dict] = {}
            for rid in self.alive_replicas():
                with self._cond:
                    state = self._replicas.get(rid)
                    if state is None or state.dead:
                        continue
                    state.draining = True
                try:
                    deadline = time.monotonic() + drain_timeout
                    while True:
                        with self._lock:
                            left = len(state.outstanding)
                        if left == 0:
                            break
                        if time.monotonic() > deadline:
                            raise MXNetError(
                                f"swap aborted: replica {rid} still has "
                                f"{left} ticket(s) in flight after "
                                f"{drain_timeout:.0f}s")
                        time.sleep(0.005)
                    reports[rid] = state.handle.swap(
                        path, drain_timeout=drain_timeout)
                    state.swaps += 1
                finally:
                    with self._cond:
                        state.draining = False
                        self._cond.notify_all()
            self._weights_step = step
            self._count("swaps")
            profiler.set_gauge("fleet.weights_step", float(step))
            return {"step": step, "path": path,
                    "replicas": reports,
                    "total_ms": (time.monotonic() - t0) * 1e3}

    # -- multi-tenant adapters ------------------------------------------
    def publish_adapter(self, name, a, b, alpha=None) -> Dict:
        """Broadcast one LoRA adapter to every live replica — HOT,
        unlike :meth:`swap_weights`: no drain, no dispatch pause (each
        engine's publish is a slab write plus one atomic reference
        swap; in-flight streams are untouched).  Returns the per-rid
        slot map.  If ANY replica refuses, the successes are rolled
        back (retired) and the error raises — an adapter is routable
        only when the whole fleet can serve it."""
        name = str(name)
        a = np.asarray(a)
        b = np.asarray(b)
        with self._cond:
            handles = {rid: s.handle
                       for rid, s in self._replicas.items()
                       if not s.dead}
        slots: Dict[int, int] = {}
        errors: Dict[int, BaseException] = {}
        for rid, handle in sorted(handles.items()):
            try:
                slots[rid] = int(handle.publish_adapter(
                    name, a, b, alpha=alpha))
            except BaseException as exc:  # noqa: BLE001 — collected
                errors[rid] = exc
        if errors:
            for rid in slots:  # roll the partial publish back
                try:
                    handles[rid].retire_adapter(name)
                except BaseException:  # noqa: BLE001 — best effort
                    pass
            detail = "; ".join(f"rid {rid}: {exc}"
                               for rid, exc in sorted(errors.items()))
            raise MXNetError(
                f"publish_adapter({name!r}) failed on "
                f"{len(errors)}/{len(handles)} replica(s) — rolled "
                f"back: {detail}")
        with self._lock:
            self._adapters.add(name)
        self._count("adapter_publishes")
        return {"name": name, "slots": slots}

    def retire_adapter(self, name) -> Dict:
        """Broadcast an adapter retire — also hot.  Replicas with live
        references defer the actual free to the last holder's
        retirement; the name stops being acquirable fleet-wide
        immediately.  Returns {rid: freed-now bool}."""
        name = str(name)
        with self._cond:
            handles = {rid: s.handle
                       for rid, s in self._replicas.items()
                       if not s.dead}
        freed: Dict[int, bool] = {}
        errors: Dict[int, BaseException] = {}
        for rid, handle in sorted(handles.items()):
            try:
                freed[rid] = bool(handle.retire_adapter(name))
            except BaseException as exc:  # noqa: BLE001 — collected
                errors[rid] = exc
        with self._lock:
            self._adapters.discard(name)
        self._count("adapter_retires")
        if errors:
            detail = "; ".join(f"rid {rid}: {exc}"
                               for rid, exc in sorted(errors.items()))
            raise MXNetError(
                f"retire_adapter({name!r}) failed on "
                f"{len(errors)}/{len(handles)} replica(s): {detail}")
        return {"name": name, "freed": freed}

    # -- disaggregated roles --------------------------------------------
    def set_role(self, rid: int, role: str,
                 drain_timeout: Optional[float] = None) -> Dict:
        """Flip one replica's disaggregated role through the same
        quiesce machinery the rolling weight swap uses: stop routing
        to it, wait for its in-flight tickets to deliver, flip, warm,
        re-admit.  Traffic redistributes around it meanwhile; a flip
        that would leave the fleet without a prefill or a decode side
        is refused (the last replica of a role never flips away)."""
        if role not in REPLICA_ROLES:
            raise MXNetError(
                f"replica role {role!r} must be one of {REPLICA_ROLES}")
        drain_timeout = (self._swap_drain_timeout if drain_timeout
                         is None else float(drain_timeout))
        with self._cond:
            state = self._replicas.get(int(rid))
            if state is None or state.dead:
                raise MXNetError(f"no live replica {rid} to re-role")
            if state.role == role:
                return {"rid": int(rid), "role": role, "flipped": False}
            if self._roles_on:
                for side in ("prefill", "decode"):
                    if state.role == side and role != side and not any(
                            s is not state and not s.dead
                            and s.role == side
                            for s in self._replicas.values()):
                        raise MXNetError(
                            f"refusing to flip replica {rid} off "
                            f"{side!r}: it is the last {side} replica "
                            "— a one-sided fleet cannot serve")
            old = state.role
            state.draining = True
        t0 = time.monotonic()
        try:
            deadline = t0 + drain_timeout
            while True:
                with self._lock:
                    left = len(state.outstanding)
                if left == 0:
                    break
                if time.monotonic() > deadline:
                    raise MXNetError(
                        f"role flip aborted: replica {rid} still has "
                        f"{left} ticket(s) in flight after "
                        f"{drain_timeout:.0f}s")
                time.sleep(0.005)
            drain_ms = (time.monotonic() - t0) * 1e3
            setter = getattr(state.handle, "set_role", None)
            if setter is None:
                raise MXNetError(
                    f"replica {rid} handle has no set_role() — it "
                    "cannot take a disaggregated role")
            setter(role)
            with self._lock:
                state.role = role
                state.role_flips += 1
            self._count("role_flips")
            _log.warning("[fleet] replica %d role %s -> %s "
                         "(drained in %.0f ms)", rid, old, role,
                         drain_ms)
            return {"rid": int(rid), "role": role, "from": old,
                    "flipped": True, "drain_ms": drain_ms,
                    "total_ms": (time.monotonic() - t0) * 1e3}
        finally:
            with self._cond:
                state.draining = False
                self._cond.notify_all()

    def autoscale_once(self) -> Optional[Dict]:
        """One evaluation of the prefill/decode split; returns the flip
        report or None.  Pressure per role = queued + in-flight work,
        weighted by the measured per-kind cost EMAs, normalized by the
        role's replica count — plus decode-pool fullness (a nearly
        full decode pool is decode pressure even at shallow queues)
        and the interactive SLO burn (a burning TTFT objective is
        prefill starvation; a burning per-token objective is decode
        starvation).  A flip needs a 2x imbalance (hysteresis — the
        drain it triggers is not free), moves ONE replica per call,
        and never strips the last replica of a role."""
        with self._lock:
            if not self._roles_on or not self._alive:
                return None
            pre = [s for s in self._replicas.values()
                   if not s.dead and s.role == "prefill"]
            dec = [s for s in self._replicas.values()
                   if not s.dead and s.role == "decode"]
            if not pre or not dec:
                return None
            # cost-EMA weights: ms of work one queued item represents
            w_pre = [v for (k, _), v in self._cost.items()
                     if k == "decode"]
            w_dec = [v for (k, _), v in self._cost.items()
                     if k == "migrate"]
            w_pre = sum(w_pre) / len(w_pre) if w_pre else 1.0
            w_dec = sum(w_dec) / len(w_dec) if w_dec else 1.0
            q_pre = sum(len(s.outstanding) for s in pre) \
                + sum(1 for t in self._pending
                      if t.spec["kind"] == "decode" and t.phase != 2)
            q_dec = sum(len(s.outstanding) for s in dec) \
                + sum(1 for t in self._pending if t.phase == 2)
            p_pre = q_pre * w_pre / len(pre)
            p_dec = q_dec * w_dec / len(dec)
            utils = [s.cache_util for s in dec
                     if s.cache_util is not None]
            if utils and max(utils) > 0.85:
                # decode pools nearly full: migrations are about to
                # stall on admission regardless of queue depth
                p_dec *= 2.0
            burn_ttft = self._slo.burn_rate("interactive", "ttft")
            burn_tpt = self._slo.burn_rate("interactive", "tpt")
            if burn_ttft > 1.0 >= burn_tpt:
                p_pre *= 2.0
            elif burn_tpt > 1.0 >= burn_ttft:
                p_dec *= 2.0
            flip_to = None
            if p_pre > 2.0 * max(p_dec, 1e-9) and len(dec) > 1:
                flip_to = "prefill"
                victim = min(dec, key=lambda s: len(s.outstanding))
            elif p_dec > 2.0 * max(p_pre, 1e-9) and len(pre) > 1:
                flip_to = "decode"
                victim = min(pre, key=lambda s: len(s.outstanding))
            if flip_to is None:
                return None
            vrid = victim.handle.rid
        report = self.set_role(vrid, flip_to)
        report["pressure"] = {"prefill": round(p_pre, 3),
                              "decode": round(p_dec, 3)}
        return report

    def _autoscale_loop(self):
        while True:
            time.sleep(self._autoscale_interval)
            with self._lock:
                if not self._alive:
                    return
            try:
                self.autoscale_once()
            except Exception as exc:  # noqa: BLE001 — keep evaluating
                _log.warning("[fleet] autoscale pass failed: %s", exc)

    # -- stats ----------------------------------------------------------
    def stats(self) -> Dict:
        summ = self._metrics.summary()
        c = summ["counters"]
        out = {k: int(c.get(k, 0)) for k in
               ("requests", "responses", "failures", "shed", "retries",
                "duplicates", "replica_deaths", "swaps")}
        lat = summ["histograms"].get("latency_ms")
        out["p50_ms"] = lat["p50"] if lat else None
        out["p90_ms"] = lat["p90"] if lat else None
        out["p99_ms"] = lat["p99"] if lat else None
        out["requests_per_s"] = summ["rates"].get("requests", 0.0)
        out["shed_rate"] = (out["shed"] / out["requests"]
                            if out["requests"] else 0.0)
        # disaggregation: migration counters + the phase-isolated
        # latency split (TTFT from the prefill side, per-token from
        # the decode side — the isolation the role split buys)
        for k in ("migrations", "migration_bytes", "re_prefills",
                  "role_flips"):
            out[k] = int(c.get(k, 0))
        out["migration_ms_total"] = round(
            float(c.get("migration_ms_total", 0.0)), 6)
        for key, hist in (("migration", "migration_ms"),
                          ("ttft", "ttft_ms"),
                          ("decode_per_token", "decode_ms_per_token")):
            h = summ["histograms"].get(hist)
            out[f"{key}_p50_ms"] = h["p50"] if h else None
            out[f"{key}_p99_ms"] = h["p99"] if h else None
        out["migrations_per_s"] = summ["rates"].get("migrations", 0.0)
        with self._lock:
            out["pending"] = len(self._pending)
            out["replicas"] = {
                rid: {"dead": s.dead, "draining": s.draining,
                      "outstanding": len(s.outstanding),
                      "swaps": s.swaps, "role": s.role,
                      "role_flips": s.role_flips,
                      "free_blocks": s.free_blocks,
                      "cache_util": s.cache_util}
                for rid, s in self._replicas.items()}
            out["disagg"] = self._roles_on and self._disagg_live()
        out["alive"] = self.alive_replicas()
        out["weights_step"] = self._weights_step
        # multi-tenancy: per-tenant fairness (requests/shed at the
        # router's own increment sites) + quota balances; fleet_top
        # renders this section only when it is non-empty
        out["shed_tenant_quota"] = int(c.get("shed_tenant_quota", 0))
        with self._lock:
            out["tenants"] = {t: dict(d)
                              for t, d in self._tenants.items()}
        if self._quota is not None:
            for t, q in self._quota.stats().items():
                out["tenants"].setdefault(t, {}).update(q)
        out["adapters_published"] = sorted(self._adapters)
        out["cost_model_ms"] = {f"{k}:{b}": round(v, 3)
                                for (k, b), v in sorted(self._cost.items())}
        out["latency_breakdown"] = self.latency_breakdown()
        # the one-glance judgment bit (full detail: /statusz "slo")
        out["slo_alert_active"] = self._slo.alert_active()
        return out

    def latency_breakdown(self) -> Dict:
        """Router-side phase percentiles from the per-request spans'
        histograms: queue_wait (per-dispatch pending wait), admission
        (submit → first dispatch), retry (failed dispatch → requeue =
        the conviction window), total (submit → delivery).  The
        engines' stats() add prefill/decode; the benches merge both
        into the JSON latency-breakdown object."""
        from .serving import _phase_breakdown

        return _phase_breakdown(
            self._metrics.summary(),
            {"queue_wait": "queue_wait_ms",
             "admission": "admission_ms",
             "retry": "retry_ms",
             "total": "latency_ms"})

    def reset_stats(self):
        """Per-sweep-point percentiles for the bench (the DecodeEngine
        convention)."""
        self._metrics.reset()

    # -- client wire ----------------------------------------------------
    def serve(self, host: str = "127.0.0.1", port: int = 0) -> int:
        """Expose the router on the fleet wire; returns the bound port.
        Clients speak :class:`FleetClient`."""
        router = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                wlock = threading.Lock()
                try:
                    while True:
                        req = wire.recv_frame(self.request)
                        router._client_dispatch(req, self.request, wlock)
                except (ConnectionError, EOFError, OSError):
                    pass

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        port = self._server.server_address[1]
        threading.Thread(target=self._server.serve_forever, daemon=True,
                         name="mxnet_tpu-fleet-router").start()
        return port

    def _client_dispatch(self, buf: memoryview, sock, wlock):
        op = buf[0]
        (rid,) = wire.U64.unpack_from(buf, 1)

        def send(fop, status, payload: bytes):
            frame = bytes([fop]) + wire.U64.pack(rid) \
                + bytes([status]) + payload
            try:
                with wlock:
                    wire.send_frame(sock, frame)
            except OSError:
                pass  # client went away; nothing to deliver to

        if op == _F_SUBMIT:
            try:
                # client SUBMIT: optional trace field, then a deadline
                # budget, then the request spec (0 = none → the router
                # default applies)
                trace, off = wire.unpack_trace(buf, 9)
                (deadline_us,) = wire.U64.unpack_from(buf, off)
                off += 8
                deadline_ms = deadline_us / 1e3 if deadline_us else None
                spec = _unpack_spec(buf, off)
                if spec["kind"] == "infer":
                    fut = self.submit(spec["inputs"],
                                      deadline_ms=deadline_ms,
                                      trace=trace)
                else:
                    # wire seed 0 = router-assigned (the deterministic
                    # ticket seed); explicit seeds pass through
                    fut = self.generate(
                        spec["prompt"], spec["max_new"],
                        temperature=spec["temperature"],
                        eos_id=spec["eos"],
                        deadline_ms=deadline_ms,
                        seed=spec["seed"] or None,
                        trace=trace,
                        slo_class=spec.get("slo_class",
                                           "interactive"),
                        tenant=spec.get("tenant"),
                        adapter=spec.get("adapter"))
            except ShedError as exc:
                send(_F_RESULT, _ST_SHED, f"{exc.reason}: {exc}".encode())
                return
            except BaseException as exc:  # noqa: BLE001 — to the wire
                send(_F_RESULT, _ST_ERR,
                     f"{type(exc).__name__}: {exc}".encode())
                return

            def done(f):
                exc = f.exception()
                if exc is None:
                    send(_F_RESULT, _ST_OK, _pack_result(f.result()))
                elif isinstance(exc, ShedError):
                    send(_F_RESULT, _ST_SHED,
                         f"{exc.reason}: {exc}".encode())
                else:
                    send(_F_RESULT, _ST_ERR,
                         f"{type(exc).__name__}: {exc}".encode())

            fut.add_done_callback(done)
            return
        if op == _F_CTRL:
            # control ops run OFF the connection's read thread: a
            # rolling swap takes minutes of drain+warmup and must not
            # stall this client's subsequent submits (the ReplicaServer
            # ctrl-thread rule)
            def ctrl():
                try:
                    _trace, off = wire.unpack_trace(buf, 9)
                    spec, _ = wire.unpack_signed_json(
                        self._secret, buf, off, "fleet control frame")
                    if spec.get("op") == "stats":
                        out = self.stats()
                    elif spec.get("op") == "swap":
                        out = self.swap_weights(spec["ckpt_dir"])
                    else:
                        raise MXNetError(
                            f"unknown router control op "
                            f"{spec.get('op')!r}")
                    send(_F_CTRL_RESULT, _ST_OK, json.dumps(out).encode())
                except BaseException as exc:  # noqa: BLE001
                    send(_F_CTRL_RESULT, _ST_ERR,
                         f"{type(exc).__name__}: {exc}".encode())

            threading.Thread(target=ctrl, daemon=True,
                             name="mxnet_tpu-fleet-router-ctrl").start()
            return
        send(_F_RESULT, _ST_ERR, f"unknown fleet op {op}".encode())

    # -- lifecycle ------------------------------------------------------
    def close(self, stop_replicas: bool = False):
        canary = getattr(self, "_canary", None)
        if canary is not None:  # stop probing BEFORE the door shuts
            canary.stop()
            self._canary = None
        with self._cond:
            if not self._alive:
                return
            self._alive = False
            pending, self._pending = self._pending, []
            self._cond.notify_all()
        for t in pending:
            if not t.delivered \
                    and t.future.set_running_or_notify_cancel():
                t.future.set_exception(MXNetError("Router closed"))
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
        for state in self._replicas.values():
            try:
                if stop_replicas and not state.dead:
                    stop = getattr(state.handle, "stop", None)
                    if stop is not None:
                        stop()
                state.handle.close()
            except Exception:  # noqa: BLE001 — teardown
                pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------


class FleetClient:
    """Client of a served :class:`Router` (the ``ps.py`` wire: length-
    prefixed frames, tensors never pickled, control payloads HMAC'd).
    Any number of requests may be in flight; responses match by id."""

    def __init__(self, host: str, port: int, secret: bytes = b"",
                 timeout: float = 30.0):
        t0 = time.monotonic()
        while True:
            try:
                sock = socket.create_connection((host, port), timeout=10)
                break
            except OSError:
                if time.monotonic() - t0 > timeout:
                    raise MXNetError(
                        f"cannot reach fleet router at {host}:{port}")
                time.sleep(0.1)
        sock.settimeout(None)
        self._secret = secret
        self._dx = _Duplex(sock, "client")
        self._dx.start()

    def submit(self, inputs: Dict[str, Any],
               deadline_ms: Optional[float] = None,
               trace=None) -> Future:
        spec = {"kind": "infer", "inputs": inputs}
        return self._begin_submit(spec, deadline_ms, trace)

    def generate(self, prompt, max_new_tokens=32, temperature=None,
                 eos_id=None, deadline_ms: Optional[float] = None,
                 trace=None, slo_class="interactive", tenant=None,
                 adapter=None) -> Future:
        spec = {"kind": "decode", "prompt": prompt,
                "max_new": max_new_tokens, "temperature": temperature,
                "eos": eos_id, "seed": 0, "slo_class": slo_class,
                "tenant": tenant, "adapter": adapter}
        fut = self._begin_submit(spec, deadline_ms, trace)
        # decode result is ONE token tensor, not a list
        out: Future = Future()

        def unwrap(f):
            exc = f.exception()
            if out.set_running_or_notify_cancel():
                if exc is not None:
                    out.set_exception(exc)
                else:
                    out.set_result(f.result()[0])

        fut.add_done_callback(unwrap)
        return out

    def _begin_submit(self, spec, deadline_ms, trace=None) -> Future:
        deadline_us = 0 if deadline_ms is None \
            else max(1, int(float(deadline_ms) * 1e3))
        # the root of the distributed trace lives HERE: the client's
        # submit→result span; everything the router and replicas stamp
        # hangs under it via the wire's optional trace field
        ctx = trace if trace is not None else profiler.make_trace()
        body = (wire.pack_trace(ctx) + wire.U64.pack(deadline_us)
                + _pack_spec(spec))
        t0 = time.perf_counter()
        fut = self._dx.begin(_F_SUBMIT, body, _parse_submit_response)
        if ctx is not None:
            def end_root(f, _t0=t0, _ctx=ctx):
                profiler.add_trace_event(
                    "client.request", _t0,
                    time.perf_counter() - _t0, _ctx, cat="fleet",
                    args={"kind": spec["kind"],
                          "ok": f.exception() is None})

            fut.add_done_callback(end_root)
        return fut

    def stats(self) -> Dict:
        return self._ctrl({"op": "stats"})

    def swap_weights(self, ckpt_dir: str) -> Dict:
        return self._ctrl({"op": "swap", "ckpt_dir": ckpt_dir},
                          timeout=3600.0)

    def _ctrl(self, obj: Dict, timeout: float = 60.0) -> Dict:
        def parse(status, payload):
            if status != _ST_OK:
                return MXNetError(bytes(payload).decode(errors="replace"))
            return json.loads(bytes(payload).decode())

        body = wire.pack_trace(None) \
            + wire.pack_signed_json(self._secret, obj)
        return self._dx.begin(_F_CTRL, body, parse).result(timeout)

    def close(self):
        self._dx.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# local fleet launcher (bench + chaos drill)
# ---------------------------------------------------------------------------


def launch_local_fleet(num_replicas: Optional[int], fleet_dir: str,
                       builder: str, builder_kwargs: Optional[Dict] = None,
                       secret: bytes = b"fleet-local", **router_kw):
    """Spawn N replica processes on this host, connect handles, return
    ``(router, procs)``.  The chaos drill's entry point: ``kill -9``
    any of ``procs`` and the router carries on."""
    n = int(fleet_env("MXNET_FLEET_REPLICAS")
            if num_replicas is None else num_replicas)
    os.makedirs(fleet_dir, exist_ok=True)
    write_secret(fleet_dir, secret)
    procs = [spawn_replica(rid, fleet_dir, builder, builder_kwargs)
             for rid in range(n)]
    handles = []
    try:
        for rid in range(n):
            host, port = read_endpoint(fleet_dir, rid)
            handles.append(ReplicaClient(rid, host, port, secret=secret))
    except BaseException:
        for p in procs:
            p.kill()
        raise
    router = Router(handles, fleet_dir=fleet_dir, secret=secret,
                    **router_kw)
    return router, procs


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m mxnet_tpu.fleet '<replica spec json>'",
              file=sys.stderr)
        return 2
    return _replica_main(json.loads(argv[0]))


if __name__ == "__main__":
    sys.exit(main())
