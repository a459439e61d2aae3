"""Serving-cluster worker: one ServingEngine behind a framed RPC loop.

``python -m paddle_tpu.serving.worker`` is the entrypoint
:class:`~paddle_tpu.serving.cluster.ClusterSupervisor` spawns — one
process per replica. Rendezvous rides the native TCPStore: the
supervisor publishes a pickled *spec* (model config + engine kwargs)
under ``<prefix>/spec``; the worker builds the model, binds an
ephemeral TCP port, publishes it under ``<prefix>/<worker-id>/port``
(pid alongside, so the supervisor can SIGKILL a partitioned worker),
and serves framed request/response RPC forever.

Protocol (one pickled dict per ``_framing`` frame). With a cluster
secret (``PTPU_CLUSTER_SECRET``, always set by the supervisor) every
accepted connection must pass the shared-secret handshake before its
first frame is parsed, and every frame carries a sequenced MAC — an
unauthenticated or tampered peer is a counted typed rejection
(``AuthError``) and the serve loop simply waits for the next
connection; the worker never crashes and never unpickles bytes that
failed authentication. The spec itself arrives sealed and is
unpickled under ``_framing.restricted_loads``'s data-only allowlist.

- every request carries ``(token, seq)``; the worker caches its last
  response per token so a client that lost a response to a partition
  can reconnect and *resend* without the operation running twice —
  the exactly-once property the router's delivery gate needs holds
  across retries, not just clean calls.
- ``step``/``drain``/``recover`` responses carry the rids the
  operation *returned* (the router delivers exactly those) plus a
  full per-rid state refresh (tokens so far, finish reason, error)
  and an engine summary (queue order, slot map, undelivered debt) —
  the client mirrors it so the router's failover can re-home
  everything from host-side state when this process dies.
- ``reset`` swaps in a fresh engine (and clears armed faults), so a
  chaos band reuses warm worker processes across episodes instead of
  paying a process spawn per seed.
- ``arm`` arms a resilience fault point in THIS process; with
  ``kill=True`` the "exception" is ``os.kill(getpid(), SIGKILL)`` —
  the mid-step hard-death kind the failover certification needs.
- ``stall`` delays every subsequent response: the hung-worker case a
  probe timeout must classify as SUSPECT, not DEAD.

Engine clock: with ``spec["virtual_clock"]`` the engine's ``time_fn``
returns the last ``now`` any RPC carried — the chaos episodes' virtual
clock spans the process boundary, so deadline laws stay deterministic.
"""
from __future__ import annotations

import argparse
import os
import pickle
import signal
import socket
import time
from typing import Any, Dict, List, Optional

__all__ = ["main", "WorkerServer"]


def _wire_error(e: BaseException) -> BaseException:
    """Best-effort typed error across the pickle boundary."""
    from .errors import RemoteError, ServingError
    if isinstance(e, ServingError):
        return e
    try:
        pickle.loads(pickle.dumps(e))
        return e
    except Exception:
        return RemoteError(type(e).__name__, str(e))


class WorkerServer:
    """The in-process half: owns the engine, dispatches ops."""

    def __init__(self, spec: Dict[str, Any], worker_id: str,
                 secret: Optional[bytes] = None):
        self.spec = spec
        self.worker_id = worker_id
        self._secret = secret
        self._clock = {"t": 0.0}
        self._virtual = bool(spec.get("virtual_clock"))
        self._stall_s = 0.0
        # (token, seq) -> response blob: resend-dedup (see module doc)
        self._last_key: Optional[tuple] = None
        self._last_blob: Optional[bytes] = None
        self._model = self._build_model(spec)
        self._apply_published_weights()
        self.engine = None
        self._reqs: Dict[int, Any] = {}
        self._trace_buf = None
        self._make_engine(spec.get("engine") or {},
                          donate=bool(spec.get("donate")))

    # -- construction --------------------------------------------------
    @staticmethod
    def _build_model(spec: Dict[str, Any]):
        import paddle_tpu as paddle
        from ..models.llama import (LlamaConfig, LlamaForCausalLM,
                                    llama_tiny_config)
        paddle.seed(int(spec.get("model_seed", 0)))
        kw = dict(spec.get("model_config") or {})
        cfg = llama_tiny_config(**kw) if spec.get("tiny", True) \
            else LlamaConfig(**kw)
        model = LlamaForCausalLM(cfg)
        model.eval()
        return model

    def _apply_published_weights(self) -> None:
        """Load parameters from the shared weight store when the spec
        carries a manifest digest. Every chunk is sha256-verified; a
        corrupt or short read is a typed retryable failure and the
        worker dies loudly rather than serve silently wrong weights."""
        w = self.spec.get("weights")
        if not w:
            return               # legacy path: seed-built weights stand
        from .weight_store import WeightStore, WeightStoreError
        state = WeightStore(w["dir"]).fetch(w["manifest"])
        missing, unexpected = self._model.set_state_dict(state)
        if missing or unexpected:
            raise WeightStoreError(
                f"published manifest does not cover the model: "
                f"missing={missing!r} unexpected={unexpected!r}")

    def _now(self) -> float:
        return self._clock["t"] if self._virtual else time.monotonic()

    def _make_engine(self, engine_kw: Dict[str, Any],
                     donate: bool = False) -> None:
        from ..distributed._framing import register_auth_failure_hook
        from ..observability import (FlightRecorder, MetricRegistry,
                                     TraceBuffer, clear_bindings,
                                     install_trace_buffer)
        from ..resilience import faults
        from .engine import ServingEngine
        faults.clear()           # episode hygiene: no armed leftovers
        clear_bindings()
        registry = MetricRegistry()
        # server-side rejections (unauthenticated clients, garbage
        # MACs) land on the worker's registry and merge through the
        # ordinary telemetry scrape
        self._m_auth = registry.counter(
            "ptpu_cluster_auth_failures_total",
            "typed auth rejections: failed handshakes, bad/replayed "
            "frame MACs, tampered rendezvous values, disallowed spec "
            "globals")
        register_auth_failure_hook(self._on_auth_failure)
        # fresh buffer per engine incarnation: counters restart at 0,
        # which the host-side merger treats as a rebaseline (the
        # supervisor calls telemetry.rebaseline after each reset).
        # An engine step writes about 13 records, so the default
        # holds about 600 steps between two scrapes
        self._trace_buf = TraceBuffer(
            capacity=int(self.spec.get("trace_capacity", 8192)),
            time_fn=self._now)
        install_trace_buffer(self._trace_buf)
        # flight ring spills to <spill_dir>/flight_<pid>.json every
        # spill_every records (and on SIGTERM), so even a SIGKILLed
        # worker leaves its last records for the supervisor's death
        # dump to attach
        spill_dir = self.spec.get("spill_dir")
        spill_path = os.path.join(
            str(spill_dir), f"flight_{os.getpid()}.json") \
            if spill_dir else None
        self.engine = ServingEngine(
            self._model, time_fn=self._now,
            registry=registry,
            flight_recorder=FlightRecorder(
                capacity=64, time_fn=self._now,
                spill_path=spill_path,
                spill_every=int(self.spec.get("spill_every", 8))),
            **engine_kw)
        if donate:
            # chaos: a step failure invalidates the cache pools, so
            # recover()/failover paths are exercised for real
            self.engine._donate = lambda: (5, 6)
        self._reqs = {}

    def _on_auth_failure(self, _reason: str) -> None:
        m = getattr(self, "_m_auth", None)
        if m is not None:
            try:
                m.inc()
            except Exception:
                pass            # a metrics hiccup must not mask the rejection

    # -- response plumbing ---------------------------------------------
    def _state(self) -> Dict[str, Any]:
        eng = self.engine
        return {
            "queued": [r.rid for r in eng.scheduler.pending()],
            "slots": {int(s): eng.cache.slots[s].rid
                      for s in eng.cache.active_slots()},
            "undelivered": [r.rid for r in eng._undelivered],
            "broken": eng._broken,
        }

    def _updates(self, extra: Optional[List] = None) -> Dict[int, dict]:
        ups: Dict[int, dict] = {}
        for req in list(self._reqs.values()) + list(extra or []):
            ups[req.rid] = {
                "out": list(req.out_tokens),
                "finished": bool(req.finished),
                "reason": req.finish_reason,
                "error": _wire_error(req.error)
                if req.error is not None else None,
                "slot": req.slot,
            }
        return ups

    def _ok(self, finished: Optional[List] = None, **extra) -> dict:
        done = finished or []
        resp = {"ok": True, "finished": [r.rid for r in done],
                "updates": self._updates(done),
                "state": self._state()}
        resp.update(extra)
        self._prune()
        return resp

    def _err(self, e: BaseException) -> dict:
        resp = {"ok": False, "error": _wire_error(e),
                "updates": self._updates(), "state": self._state()}
        self._prune()
        return resp

    def _prune(self) -> None:
        # terminal requests were reported (and the blob is cached for
        # a resend) — drop them so updates stay O(in-flight); their
        # trace bindings go with them (bounded binding table)
        from ..observability import unbind_request
        for rid, r in self._reqs.items():
            if r.finished:
                unbind_request(rid)
        self._reqs = {rid: r for rid, r in self._reqs.items()
                      if not r.finished}

    @staticmethod
    def _bind_trace(req) -> None:
        # the router minted req.trace before the dispatch RPC; bind
        # rid → context so engine spans (which only carry request_id)
        # join the request's distributed trace
        from ..observability import bind_request
        bind_request(req.rid, getattr(req, "trace", None))

    def _mark_cancels(self, msg: dict) -> None:
        # the client's FrontDoor flags disconnects on ITS Request
        # objects; forward the flags so the engine's own sweep runs
        # the real mid-prefill/mid-handoff abort paths
        for rid in msg.get("cancel_rids") or ():
            req = self._reqs.get(rid)
            if req is not None:
                req.cancel_requested = True

    # -- ops -----------------------------------------------------------
    def dispatch(self, msg: dict) -> dict:
        if "now" in msg and msg["now"] is not None:
            self._clock["t"] = float(msg["now"])
        op = msg["op"]
        eng = self.engine
        try:
            if op == "probe":
                from ..distributed._framing import auth_failures
                health = eng.probe()
                # process-wide rejection count: the unauth-client test
                # asserts it through an AUTHENTICATED probe
                health["auth_failures"] = auth_failures()
                return self._ok(pid=os.getpid(), health=health)
            if op == "submit":
                req = msg["req"]
                self._bind_trace(req)
                eng.submit_request(req)
                self._reqs[req.rid] = req
                return self._ok()
            if op == "adopt":
                req = msg["req"]
                self._bind_trace(req)
                eng.adopt(req)
                self._reqs[req.rid] = req
                return self._ok()
            if op == "step":
                self._mark_cancels(msg)
                if not eng.has_work():
                    return self._ok()
                return self._ok(finished=eng.step())
            if op == "recover":
                report = eng.recover()
                return self._ok(finished=report["finished"])
            if op == "drain":
                self._mark_cancels(msg)
                return self._ok(finished=eng.drain(msg.get("max_steps")))
            if op == "cancel":
                req = self._reqs.get(msg["rid"])
                hit = req is not None and \
                    eng.cancel(req, msg.get("reason", "cancelled"))
                return self._ok(finished=[req] if hit else None,
                                cancelled=bool(hit))
            if op == "unqueue":
                # drain_replica: queued requests move to peers NOW
                from ..observability import unbind_request
                moved = eng.scheduler.drain()
                for r in moved:
                    self._reqs.pop(r.rid, None)
                    unbind_request(r.rid)
                return self._ok(moved=[r.rid for r in moved])
            if op == "requeue":
                req = msg["req"]
                self._bind_trace(req)
                eng.scheduler.requeue(req)
                self._reqs[req.rid] = req
                return self._ok()
            if op == "telemetry":
                buf = self._trace_buf
                payload = {
                    "pid": os.getpid(), "now": self._now(),
                    "spans": buf.drain() if buf is not None else [],
                    "drained_total":
                        buf.drained_total if buf is not None else 0,
                    "dropped_total":
                        buf.dropped_total if buf is not None else 0,
                    "recorded_total":
                        buf.recorded_total if buf is not None else 0,
                    "registry": eng.registry.to_json()}
                return self._ok(telemetry=payload)
            if op == "audit":
                from ..resilience.invariants import (
                    engine_leak_violations, page_leak_violations)
                v = engine_leak_violations(eng) \
                    + page_leak_violations(eng)
                return self._ok(violations=v,
                                trace_counts=eng.trace_counts)
            if op == "reset":
                # re-verify the published weights BEFORE _make_engine
                # clears armed faults, so a chaos arm on
                # cluster.weights.fetch lands on this exact fetch; a
                # failure past the retry budget is a typed refusal and
                # the supervisor hard-respawns instead of soft-reclaim
                self._apply_published_weights()
                self._make_engine(msg.get("engine") or {},
                                  donate=bool(msg.get("donate")))
                self._virtual = bool(msg.get("virtual_clock",
                                             self._virtual))
                self._stall_s = 0.0
                return self._ok()
            if op == "stall":
                self._stall_s = float(msg.get("seconds", 0.0))
                return self._ok()
            if op == "arm":
                from ..resilience import faults
                if msg.get("kill"):
                    def _suicide(*_a, **_k):
                        os.kill(os.getpid(), signal.SIGKILL)
                    exc = _suicide
                else:
                    exc = None
                faults.inject(msg["point"],
                              times=msg.get("times", 1),
                              after=msg.get("after", 0), exc=exc)
                return self._ok()
            raise ValueError(f"unknown worker op {op!r}")
        except Exception as e:  # typed refusal, not a dead worker
            return self._err(e)

    # -- the serve loop ------------------------------------------------
    def serve(self, srv: socket.socket) -> None:
        from ..distributed._framing import (nodelay, recv_msg,
                                            send_msg, server_handshake)
        while True:
            conn, _ = srv.accept()
            nodelay(conn)
            auth = None
            try:
                if self._secret is not None:
                    # a peer that cannot pass the handshake — an
                    # unauthenticated client, a wrong secret, garbage
                    # bytes — raises a counted typed AuthError here
                    # (a ConnectionError): this connection dies, the
                    # loop accepts the next one, no frame of it was
                    # ever unpickled
                    auth = server_handshake(conn, self._secret)
                while True:
                    blob = recv_msg(conn, eof_ok=True, auth=auth)
                    if blob is None:
                        break
                    msg = pickle.loads(blob)
                    key = (msg.get("token"), msg.get("seq"))
                    stall = self._stall_s
                    if key == self._last_key \
                            and self._last_blob is not None:
                        out = self._last_blob   # resend, don't re-run
                    elif msg.get("op") == "shutdown":
                        send_msg(conn, pickle.dumps(
                            {"ok": True, "seq": msg.get("seq")}),
                            auth=auth)
                        os._exit(0)
                    else:
                        resp = self.dispatch(msg)
                        resp["seq"] = msg.get("seq")
                        try:
                            out = pickle.dumps(resp)
                        except Exception as e:
                            out = pickle.dumps(
                                {"ok": False, "seq": msg.get("seq"),
                                 "error": _wire_error(e)})
                        self._last_key, self._last_blob = key, out
                    if stall:
                        time.sleep(stall)
                    send_msg(conn, out, auth=auth)
            except (ConnectionError, OSError):
                pass             # client gone/rejected; await the next
            finally:
                try:
                    conn.close()
                except OSError:
                    pass


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        description="paddle_tpu serving-cluster worker")
    parser.add_argument("--store-host", default="127.0.0.1")
    parser.add_argument("--store-port", type=int, required=True)
    parser.add_argument("--prefix", required=True)
    parser.add_argument("--worker-id", required=True)
    parser.add_argument("--bind-host", default="127.0.0.1",
                        help="local interface the RPC server binds")
    parser.add_argument("--advertise-host", default=None,
                        help="address published for peers to dial "
                             "(defaults to --bind-host)")
    args = parser.parse_args(argv)
    advertise = args.advertise_host or args.bind_host
    # the supervisor always exports the cluster secret into this
    # process's environment; absent = legacy unauthenticated framing
    secret_env = os.environ.get("PTPU_CLUSTER_SECRET", "")
    secret = secret_env.encode("utf-8", "surrogateescape") \
        if secret_env else None

    from ..distributed._framing import open_sealed, restricted_loads
    from ..distributed.store import TCPStore
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    store = TCPStore(args.store_host, args.store_port,
                     is_master=False, world_size=1)
    spec_key = f"{args.prefix}/spec"
    blob = store.get(spec_key, timeout=60.0)
    if secret is not None:
        blob = open_sealed(secret, spec_key, blob)
    # data-only allowlist regardless of sealing: the spec never needs
    # to execute code, so it never gets to
    spec = restricted_loads(blob)
    server = WorkerServer(spec, args.worker_id, secret=secret)

    def _sigterm(_signum, _frame):
        # graceful kill: spill the flight ring so the supervisor's
        # death dump can attach it, then exit hard (the serve loop
        # holds no state worth unwinding)
        try:
            rec = getattr(server.engine, "recorder", None)
            if rec is not None:
                rec.spill()
        finally:
            os._exit(0)

    signal.signal(signal.SIGTERM, _sigterm)

    from ..distributed._framing import seal
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind((args.bind_host, 0))
    srv.listen(8)
    port = srv.getsockname()[1]

    def publish(key: str, value: bytes) -> None:
        store.set(key, seal(secret, key, value)
                  if secret is not None else value)

    publish(f"{args.prefix}/{args.worker_id}/pid",
            str(os.getpid()).encode())
    publish(f"{args.prefix}/{args.worker_id}/host",
            advertise.encode("utf-8"))
    # port LAST: the supervisor waits on it, so host/pid are already
    # readable when the wait returns
    publish(f"{args.prefix}/{args.worker_id}/port",
            str(port).encode())
    store.close()
    server.serve(srv)


if __name__ == "__main__":
    main()
