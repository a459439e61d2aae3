"""Cross-process DistModel: one OS process per pipeline stage.

Reference: paddle/fluid/distributed/fleet_executor/dist_model.cc — each
RANK is a process that loads its program partition; Run() feeds rank 0,
activations flow rank->rank over brpc, fetch comes from the last rank.

TPU-native version: every stage is an exported StableHLO artifact
served by a Predictor inside its own ``python -m
paddle_tpu.inference.dist_model_mp`` worker process (own XLA runtime,
own device context — the process isolation the in-process
``DistModel`` actors do not give). Activations travel stage->stage
over persistent length-prefixed sockets (the rpc/tcp_store transport
family, csrc/tcp_store.cc style framing), so stage k runs micro-batch
i while stage k+1 runs micro-batch i-1. The driver keeps a credit
window of in-flight micro-batches for backpressure, like the
interceptor buffer_size in the in-process engine.
"""
from __future__ import annotations

import os
import pickle
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Any, List, Sequence

import numpy as np

from .dist_model import DistModelConfig

__all__ = ["DistModelMP", "DistModelConfig"]


from ..distributed._framing import (nodelay as _nodelay,
                                    send_msg, recv_msg)


def _send(sock: socket.socket, obj) -> None:
    send_msg(sock, pickle.dumps(obj,
                                protocol=pickle.HIGHEST_PROTOCOL))


def _recv(sock: socket.socket):
    try:
        data = recv_msg(sock, eof_ok=True)
    except ConnectionError:
        return None
    return None if data is None else pickle.loads(data)


def _worker_main(model_prefix: str, listen_port: int, next_addr: str,
                 precision: str) -> None:
    """One pipeline stage: serve Predictor.run over the socket chain."""
    from . import Config, create_predictor, PrecisionType

    cfg = Config(model_prefix)
    if precision == "int8":
        cfg.set_precision(PrecisionType.Int8)
    elif precision == "half":
        cfg.set_precision(PrecisionType.Half)
    pred = create_predictor(cfg)

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", listen_port))
    srv.listen(1)
    # readiness handshake: the driver connects only after the stage
    # printed its port (predictor load can take seconds)
    sys.stdout.write(f"READY {srv.getsockname()[1]}\n")
    sys.stdout.flush()

    nxt = None
    if next_addr:
        host, port = next_addr.rsplit(":", 1)
        deadline = time.time() + 60
        while True:
            try:
                nxt = _nodelay(socket.create_connection((host, int(port))))
                break
            except OSError:
                if time.time() > deadline:
                    raise
                time.sleep(0.05)

    conn, _ = srv.accept()
    _nodelay(conn)
    try:
        # diagnostic dwell per micro-batch: lets a 1-core host DEMONSTRATE
        # the pipeline's stage overlap (sleeps overlap where CPU-bound
        # compute cannot; tests/test_dist_model_mp.py asserts the
        # (M + S - 1) x dwell pipelined wall against the M x S serial one).
        # Honored ONLY under an explicit debug marker or on the cpu
        # platform — an operator inheriting the env var from a test
        # session must not silently slow every production request.
        dwell_s = float(os.environ.get("PTPU_STAGE_DWELL_MS", "0")) / 1e3
        if dwell_s:
            import jax
            if not (os.environ.get("PTPU_STAGE_DWELL_DEBUG")
                    or jax.default_backend() == "cpu"):
                sys.stderr.write(
                    "PTPU_STAGE_DWELL_MS set but ignored: stage runs on "
                    f"'{jax.default_backend()}' and "
                    "PTPU_STAGE_DWELL_DEBUG is unset\n")
                dwell_s = 0.0
            else:
                sys.stderr.write(  # log once, loudly — never silent
                    f"stage dwell ACTIVE: {dwell_s * 1e3:.0f} ms per "
                    "micro-batch (PTPU_STAGE_DWELL_MS diagnostic)\n")
            sys.stderr.flush()
        while True:
            msg = _recv(conn)
            if msg is None or msg[0] == "stop":
                break
            tag, payload = msg
            outs = pred.run([np.asarray(x) for x in payload])
            outs = [o.copy_to_cpu() for o in outs]
            if dwell_s:
                time.sleep(dwell_s)
            _send(nxt if nxt is not None else conn, (tag, outs))
        if nxt is not None:
            _send(nxt, ("stop", None))
    finally:
        conn.close()
        if nxt is not None:
            nxt.close()
        srv.close()


class DistModelMP:
    """Serve pipeline stages across PROCESSES (dist_model.cc Run).

    The driver connects to stage 0 and receives fetches from the LAST
    stage; intermediate activations never pass through the driver."""

    def __init__(self, config: DistModelConfig):
        self._config = config
        self._procs: List[subprocess.Popen] = []
        self._feed_sock = None
        self._fetch_srv = None
        self._fetch_sock = None
        self._initialized = False

    def init(self) -> bool:
        if self._initialized:
            return True
        n = len(self._config.model_prefixes)
        precision = ""
        p = self._config.precision
        if p is not None:
            precision = getattr(p, "name", str(p)).lower()
            precision = {"int8": "int8", "half": "half"}.get(
                precision, "")
        # the LAST stage sends fetches back to the driver
        self._fetch_srv = socket.socket(socket.AF_INET,
                                        socket.SOCK_STREAM)
        self._fetch_srv.setsockopt(socket.SOL_SOCKET,
                                   socket.SO_REUSEADDR, 1)
        self._fetch_srv.bind(("127.0.0.1", 0))
        self._fetch_srv.listen(1)
        fetch_port = self._fetch_srv.getsockname()[1]

        # workers are CPU processes unless PTPU_DIST_MODEL_PLATFORM
        # says otherwise; repo imports ride sys.argv[4] below
        repo = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = os.environ.get(
            "PTPU_DIST_MODEL_PLATFORM", "cpu")
        ports: List[int] = []
        try:
            # spawn back to front so each stage can name its successor
            for i in reversed(range(n)):
                nxt = f"127.0.0.1:{fetch_port}" if i == n - 1 \
                    else f"127.0.0.1:{ports[-1]}"
                proc = subprocess.Popen(
                    [sys.executable, "-c",
                     "import sys; sys.path.insert(0, sys.argv[4]); "
                     "from paddle_tpu.inference.dist_model_mp import "
                     "_worker_main; _worker_main(sys.argv[1], 0, "
                     "sys.argv[2], sys.argv[3])",
                     self._config.model_prefixes[i], nxt, precision,
                     repo],
                    env=env, stdout=subprocess.PIPE, stderr=sys.stderr,
                    text=True, cwd=repo)
                self._procs.append(proc)
                import select
                ready, _, _ = select.select([proc.stdout], [], [],
                                            120.0)
                line = proc.stdout.readline().strip() if ready else ""
                if not line.startswith("READY "):
                    raise RuntimeError(
                        f"stage {i} failed to start "
                        f"({'timeout' if not ready else line!r})")
                ports.append(int(line.split()[1]))
            self._procs.reverse()
            ports.reverse()
            self._fetch_srv.settimeout(120.0)
            self._feed_sock = _nodelay(socket.create_connection(
                ("127.0.0.1", ports[0]), timeout=120.0))
            self._feed_sock.settimeout(None)
            self._fetch_sock, _ = self._fetch_srv.accept()
            _nodelay(self._fetch_sock)
        except Exception:
            self._teardown()   # no orphan workers on partial failure
            raise
        self._initialized = True
        return True

    def _teardown(self):
        for p in self._procs:
            if p.poll() is None:
                p.kill()
            try:
                p.wait(timeout=5)   # reap — no zombies left behind
            except subprocess.TimeoutExpired:
                pass
        for s in (self._feed_sock, self._fetch_sock, self._fetch_srv):
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        self._procs = []
        self._feed_sock = self._fetch_sock = self._fetch_srv = None
        self._initialized = False

    def run(self, feed: Sequence[Any],
            timeout: float = 300.0) -> List[np.ndarray]:
        if not self._initialized:
            self.init()
        M = self._config.num_micro_batches
        feed = [np.asarray(getattr(x, "_data", x)) for x in feed]
        B = feed[0].shape[0]
        if B % M:
            raise ValueError(
                f"batch {B} not divisible by {M} micro-batches")
        micro = [[x[i * (B // M):(i + 1) * (B // M)] for x in feed]
                 for i in range(M)]
        window = len(self._procs) + self._config.buffer_size
        results: dict = {}
        err: list = []

        def collect():
            try:
                # the LAST stage always connects back to the fetch
                # server (even when it is also the first stage)
                while len(results) < M:
                    msg = _recv(self._fetch_sock)
                    if msg is None:
                        raise ConnectionError("pipeline closed early")
                    tag, outs = msg
                    results[tag] = outs
            except Exception as e:  # surfaced by the main thread
                err.append(e)

        t = threading.Thread(target=collect, daemon=True)
        t.start()
        sent = 0
        deadline = time.time() + timeout
        while sent < M:
            while sent - len(results) >= window and not err:
                if time.time() > deadline:
                    raise TimeoutError("DistModelMP.run timed out")
                time.sleep(0.001)
            if err:
                break
            # a wedged stage must not block sendall past the deadline
            self._feed_sock.settimeout(
                max(0.01, deadline - time.time()))
            try:
                _send(self._feed_sock, (sent, micro[sent]))
            except socket.timeout:
                err.append(TimeoutError("DistModelMP.run timed out"))
                break
            finally:
                self._feed_sock.settimeout(None)
            sent += 1
        t.join(timeout=max(0.0, deadline - time.time()))
        if err or len(results) < M or t.is_alive():
            # the collector may still hold the fetch socket: a retry
            # with two readers would interleave frames — rebuild the
            # pipeline instead (init() runs again on the next call)
            self._teardown()
            if err and not isinstance(err[0], TimeoutError):
                raise err[0]
            raise TimeoutError("DistModelMP.run timed out")
        first = results[0]
        ordered = [results[i] for i in range(M)]
        return [np.concatenate([np.asarray(o[j]) for o in ordered])
                for j in range(len(first))]

    def close(self):
        if not self._initialized:
            return
        try:
            _send(self._feed_sock, ("stop", None))
        except OSError:
            pass
        for p in self._procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass   # _teardown kills whatever is left
        self._teardown()

    def __enter__(self):
        self.init()
        return self

    def __exit__(self, *exc):
        self.close()
