"""Process-isolated serving worker: one frozen executable per process.

``python -m paddle_tpu.serving.worker --model-dir D --ready-file F`` is
the child half of the process replica fleet (``serving/fleet.py``): it
loads a saved frozen model (program + checkpointed params) into its own
Scope/Executor, warms the configured batch buckets, then serves batches
over a length-prefixed socket protocol until told to stop. Process
isolation is the point — one GIL, one heap, one fault blast radius per
replica, so a SIGKILL (or a native crash) takes out exactly one worker
and the parent's supervisor respawns it while traffic fails over.

**Framing.** Every message is an 8-byte big-endian length followed by a
pickled payload dict. :func:`send_msg` / :func:`recv_msg` are the whole
wire format; both refuse frames above ``max_frame`` (default 64 MiB,
``PADDLE_TPU_MAX_FRAME_BYTES``) and surface torn reads as a typed
:class:`TransportError` — a peer death mid-frame is an error, never a
hang. Both sides pass the ``serving.transport.send`` /
``serving.transport.recv`` chaos seams, so transport failure (raise or
hang kinds) is injectable without killing a process.

**Protocol.** Requests carry a per-message ``id`` the reply must echo —
after an attempt timeout abandons a batch, a late straggler reply on the
same connection is recognized as stale by id and discarded instead of
desynchronizing the stream. Kinds: ``run`` (one padded bucket batch;
reply ``result`` with the fetch outputs or ``error`` with the typed
exception name), ``warmup`` (same dispatch, warmup accounting),
``ping``/``pong`` (liveness + stats), ``shutdown`` (reply ``bye``, exit
0 — the deliberate scale-in path).

**Contracts honored.** The worker publishes PR-3 heartbeats
(``hb_rank{K}`` via ``PADDLE_HEARTBEAT_DIR``; per-batch beats plus a
periodic idle ``touch`` so an idle worker is never mistaken for hung)
and PR-16 telemetry journals (``PADDLE_TPU_TELEMETRY_DIR``, auto-wired
by ``Executor.__init__``), and rides the SIGTERM→drain→exit-75
preemption contract: SIGTERM finishes the in-flight batch, stops
accepting, and exits ``PREEMPTION_EXIT_CODE``. An explicit ``--port``
that loses a bind race (double spawn, stale owner) falls back to an
ephemeral port and reports the REAL port in the ready file
(``serving.worker.port_fallbacks``) instead of dying or serving nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import socket
import struct
import sys
import tempfile
import threading
import time

from ..errors import UnavailableError

__all__ = [
    "MAX_FRAME_ENV",
    "TransportError",
    "bind_serving_socket",
    "default_max_frame",
    "recv_msg",
    "send_msg",
    "worker_main",
]

_HEADER = struct.Struct("!Q")
MAX_FRAME_ENV = "PADDLE_TPU_MAX_FRAME_BYTES"
_DEFAULT_MAX_FRAME = 64 * 1024 * 1024


class TransportError(UnavailableError):
    """Worker transport failure: torn frame, oversized frame, or a peer
    that vanished mid-message. An UnavailableError, so the replica-set
    failover machinery classifies it as retryable-on-another-replica."""


def default_max_frame():
    try:
        return int(os.environ.get(MAX_FRAME_ENV, _DEFAULT_MAX_FRAME))
    except ValueError:
        return _DEFAULT_MAX_FRAME


def send_msg(sock, obj, max_frame=None):
    """Frame + send one message dict. Refuses payloads above `max_frame`
    BEFORE writing anything, so an oversized batch can never leave a
    half-written frame poisoning the stream."""
    from ..resilience.faults import fault_point

    fault_point("serving.transport.send")
    limit = default_max_frame() if max_frame is None else int(max_frame)
    payload = pickle.dumps(obj, protocol=4)
    if len(payload) > limit:
        raise TransportError(
            f"refusing to send {len(payload)}-byte frame "
            f"(max_frame {limit}); batch too large for the transport"
        )
    try:
        sock.sendall(_HEADER.pack(len(payload)) + payload)
    except socket.timeout:
        # a timeout is NOT a transport failure: the caller classifies it
        # (the fleet client types it ExecutionTimeoutError, the worker's
        # idle loop just polls again)
        raise
    except OSError as exc:
        raise TransportError(f"send failed: {exc}") from exc


def _recv_exact(sock, n, allow_eof=False):
    """Read exactly `n` bytes. Clean EOF before the first byte returns
    None when `allow_eof` (the peer closed between frames); EOF anywhere
    else is a torn frame and raises typed."""
    buf = bytearray()
    while len(buf) < n:
        try:
            chunk = sock.recv(n - len(buf))
        except socket.timeout:
            # at a frame boundary (allow_eof marks the header read) and
            # zero bytes in: a pure idle timeout, safe to poll again —
            # anywhere else the stream is desynchronized mid-message
            if allow_eof and not buf:
                raise
            raise TransportError(
                f"timed out mid-frame ({len(buf)}/{n} bytes read); "
                "stream desynchronized"
            )
        except OSError as exc:
            raise TransportError(f"recv failed: {exc}") from exc
        if not chunk:
            if allow_eof and not buf:
                return None
            raise TransportError(
                f"peer closed mid-frame ({len(buf)}/{n} bytes read); "
                "torn message"
            )
        buf.extend(chunk)
    return bytes(buf)


def recv_msg(sock, max_frame=None):
    """Receive one framed message dict, or None on clean EOF at a frame
    boundary. A length prefix above `max_frame` is refused typed (the
    connection is unusable afterwards — the caller must close it)."""
    from ..resilience.faults import fault_point

    fault_point("serving.transport.recv")
    limit = default_max_frame() if max_frame is None else int(max_frame)
    head = _recv_exact(sock, _HEADER.size, allow_eof=True)
    if head is None:
        return None
    (length,) = _HEADER.unpack(head)
    if length > limit:
        raise TransportError(
            f"refusing {length}-byte frame (max_frame {limit}); "
            "oversized or corrupt length prefix"
        )
    payload = _recv_exact(sock, length)
    try:
        return pickle.loads(payload)
    except Exception as exc:
        raise TransportError(f"undecodable frame: {exc}") from exc


def bind_serving_socket(host="127.0.0.1", port=0, backlog=4):
    """Bind + listen; an explicit `port` that is already taken (double
    spawn, stale owner holding it) falls back to an ephemeral one instead
    of dying — the ready file carries the REAL port, so the parent never
    needed the requested number to be honored. Returns (socket, port)."""
    from .. import observability as _obs

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        srv.bind((host, int(port)))
    except OSError:
        if not port:
            srv.close()
            raise
        _obs.add("serving.worker.port_fallbacks")
        print(
            f"[serving.worker] port {port} unavailable; "
            "falling back to an ephemeral port",
            file=sys.stderr,
        )
        srv.bind((host, 0))
    srv.listen(backlog)
    return srv, srv.getsockname()[1]


def _write_ready(path, payload):
    """Atomic temp+replace publish (the PR-2 idiom): the parent polling
    for readiness never reads a torn JSON."""
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(path) or ".", prefix=".ready.tmp."
    )
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.serving.worker")
    p.add_argument("--model-dir", required=True,
                   help="FrozenModel.save() directory (program + params)")
    p.add_argument("--ready-file", required=True,
                   help="where to publish {pid, port, contract} once "
                        "listening, loaded, and warm")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="listen port (0 = ephemeral; a taken explicit "
                        "port falls back to ephemeral)")
    p.add_argument("--name", default="w0", help="replica name (logs)")
    p.add_argument("--warm-buckets", default="",
                   help="comma-separated batch sizes to warm (compile) "
                        "before publishing readiness — a respawned "
                        "worker re-warms itself here, so it rejoins "
                        "rotation hot")
    p.add_argument("--attempt", type=int, default=0,
                   help="restart attempt number (supervisor bookkeeping)")
    p.add_argument("--publish-dir", default="",
                   help="live model publish dir to subscribe to; on "
                        "(re)spawn the worker catches up to the newest "
                        "committed version BEFORE publishing readiness, "
                        "so a corpse killed mid-apply rejoins bitwise "
                        "equal to a cold load of that version")
    p.add_argument("--publish-poll", type=float, default=0.5,
                   help="seconds between publish-dir polls in follow "
                        "mode (updates apply between batches — the "
                        "torn-read fence)")
    p.add_argument("--publish-mode", default="follow",
                   choices=("follow", "managed"),
                   help="follow: auto-apply new versions between "
                        "batches; managed: apply only on explicit "
                        "apply_update messages (canaried rollout)")
    return p.parse_args(argv)


class _WorkerState:
    """The loaded model + serving loop state for one worker process."""

    def __init__(self, args):
        from ..framework.executor import Executor
        from ..framework.scope import Scope
        from .freeze import load_frozen
        from .router import FrozenRunner

        self.args = args
        self.scope = Scope()
        self.executor = Executor()
        frozen = load_frozen(
            args.model_dir, scope=self.scope, executor=self.executor
        )
        self.runner = FrozenRunner(
            frozen, executor=self.executor, scope=self.scope
        )
        self.batches = 0
        self.draining = threading.Event()
        self.heartbeat = self._make_heartbeat()
        # live publish plane: subscribe BEFORE warmup/readiness, so a
        # respawned corpse (even one SIGKILLed mid-apply) rejoins on the
        # last committed version — cold frozen load + committed chain =
        # bitwise-equal to a cold load of that version by construction
        self.subscriber = None
        self._follow = False
        self._poll_s = max(0.05, float(getattr(args, "publish_poll", 0.5)))
        self._next_poll = 0.0
        publish_dir = getattr(args, "publish_dir", "")
        if publish_dir:
            from ..fleet.publish import ModelSubscriber

            self.subscriber = ModelSubscriber(
                publish_dir, main_program=frozen.program,
                scope=self.scope, heartbeat=self.heartbeat,
                name=args.name,
            )
            self._follow = getattr(
                args, "publish_mode", "follow"
            ) == "follow"
            self.subscriber.poll()
        # warm the configured buckets NOW, before readiness: a cold
        # worker entering rotation would pay its compiles inside a
        # user-visible request (the PR-6 warmup lesson), and a respawned
        # corpse re-warms here with no parent involvement
        buckets = [
            int(b) for b in args.warm_buckets.split(",") if b.strip()
        ]
        for b in buckets:
            self.runner.run(self._zero_feed(b))
        self.warmed = tuple(buckets)

    def _zero_feed(self, batch):
        import numpy as np

        from ..core.dtypes import to_numpy_dtype

        feed = {}
        for name in self.runner.feed_names:
            shape, dtype = self.runner.sample_spec(name)
            feed[name] = np.zeros((batch,) + shape, to_numpy_dtype(dtype))
        return feed

    def _rewarm(self):
        """Re-compile the warmed buckets after a shape-changing apply —
        outside any measured request (the satellite-2 contract)."""
        from .. import observability as _obs

        for b in self.warmed:
            try:
                self.runner.run(self._zero_feed(b))
            except Exception:
                break
        if self.warmed:
            _obs.add("serving.worker.rewarms")

    def _after_apply(self):
        if self.subscriber is not None and self.subscriber.shapes_changed:
            self._rewarm()

    def maybe_follow(self):
        """Follow-mode poll, called ONLY between protocol messages — the
        serve loop is single-threaded, so this placement IS the epoch
        fence: no batch can observe a half-applied version."""
        from .. import observability as _obs

        if self.subscriber is None or not self._follow:
            return None
        now = time.monotonic()
        if now < self._next_poll:
            return None
        self._next_poll = now + self._poll_s
        try:
            applied = self.subscriber.poll()
        except Exception:
            # the fence restored the old version; retry next poll (an
            # injected once-only fault heals, a bad bundle gets blocked
            # by the rollout controller)
            _obs.add("publish.follow_failures")
            return None
        if applied is not None:
            self._after_apply()
        return applied

    def digest(self):
        """CRC32 per scope-resident persistable of the frozen program —
        the cross-process bitwise-equality surface (CI compares a
        delta-updated worker against a cold fold of the same version)."""
        from .. import io as _io

        out = {}
        for var in self.runner.frozen.program.list_vars():
            if not getattr(var, "persistable", False) or getattr(
                var, "is_data", False
            ):
                continue
            val = self.scope.find_var(var.name)
            if val is not None:
                out[var.name] = _io._array_entry(val)["crc32"]
        return out

    def _make_heartbeat(self):
        from ..resilience.health import HEARTBEAT_DIR_ENV, Heartbeat

        if not os.environ.get(HEARTBEAT_DIR_ENV):
            return None
        return Heartbeat()

    def contract(self):
        """The runner surface the parent needs without loading the model:
        feed/fetch names and per-sample specs (dtype as a numpy name)."""
        from ..core.dtypes import convert_dtype

        return {
            "feed_names": list(self.runner.feed_names),
            "fetch_names": list(self.runner.fetch_names),
            "sample_specs": {
                n: [list(self.runner.sample_spec(n)[0]),
                    convert_dtype(self.runner.sample_spec(n)[1])]
                for n in self.runner.feed_names
            },
            "warmed_buckets": list(self.warmed),
        }

    def handle(self, msg):
        """Dispatch one protocol message -> reply dict (never raises for
        model-side failures: those travel as typed ``error`` replies)."""
        from .. import observability as _obs

        kind = msg.get("kind")
        mid = msg.get("id")
        if kind in ("run", "warmup"):
            try:
                outs = self.runner.run(msg["feed"])
            except Exception as exc:  # typed name travels; process lives
                _obs.add("serving.worker.batch_errors")
                return {
                    "kind": "error", "id": mid,
                    "etype": type(exc).__name__, "msg": str(exc),
                }
            self.batches += 1
            _obs.add("serving.worker.batches")
            if self.heartbeat is not None:
                try:
                    self.heartbeat.beat()
                except Exception:
                    pass  # a broken beat must not fail a served batch
            return {"kind": "result", "id": mid, "outs": list(outs)}
        if kind == "ping":
            pong = {
                "kind": "pong", "id": mid, "pid": os.getpid(),
                "batches": self.batches,
            }
            if self.subscriber is not None:
                pong["model_version"] = self.subscriber.version
                pong["staleness_s"] = self.subscriber.staleness_s()
            return pong
        if kind == "apply_update":
            # handled between batches by construction (one message at a
            # time on this loop) — the same fence follow-mode polls use
            if self.subscriber is None:
                return {
                    "kind": "error", "id": mid,
                    "etype": "PreconditionNotMetError",
                    "msg": "worker has no --publish-dir subscription",
                }
            version = msg.get("version")
            try:
                applied = (
                    self.subscriber.apply_version(version)
                    if version is not None else self.subscriber.poll()
                )
            except Exception as exc:
                _obs.add("serving.worker.apply_errors")
                return {
                    "kind": "error", "id": mid,
                    "etype": type(exc).__name__, "msg": str(exc),
                }
            if applied is not None:
                self._after_apply()
            return {
                "kind": "applied", "id": mid, "applied": applied,
                "version": self.subscriber.version,
                "staleness_s": self.subscriber.staleness_s(),
                "shapes_changed": bool(self.subscriber.shapes_changed),
            }
        if kind == "digest":
            reply = {"kind": "digest", "id": mid, "crc": self.digest()}
            if self.subscriber is not None:
                reply["version"] = self.subscriber.version
            return reply
        if kind == "shutdown":
            return {"kind": "bye", "id": mid}
        return {
            "kind": "error", "id": mid, "etype": "InvalidArgumentError",
            "msg": f"unknown message kind {kind!r}",
        }


def _idle_pulse(state, interval):
    """Daemon: periodic heartbeat ``touch`` so an idle worker (no batches,
    hence no per-batch beats) is never declared hung by the supervisor's
    stale-beat watchdog."""
    while not state.draining.wait(interval):
        if state.heartbeat is not None:
            try:
                state.heartbeat.touch()
            except Exception:
                pass


def worker_main(argv=None):
    from ..resilience.health import PREEMPTION_EXIT_CODE

    from ..core import compile_cache

    args = parse_args(argv)
    srv, port = bind_serving_socket(args.host, args.port)
    # a respawned worker re-warms its buckets from the compile cache
    compile_cache.enable()
    state = _WorkerState(args)

    import signal as _signal

    def _on_sigterm(signum, frame):
        # drain contract: finish the in-flight batch (the serve loop
        # checks the flag between messages), then exit 75
        state.draining.set()

    _signal.signal(_signal.SIGTERM, _on_sigterm)
    threading.Thread(
        target=_idle_pulse, args=(state, 1.0), daemon=True,
        name="worker-idle-pulse",
    ).start()
    if state.heartbeat is not None:
        state.heartbeat.touch()

    _write_ready(args.ready_file, {
        "pid": os.getpid(), "host": args.host, "port": port,
        "name": args.name, "attempt": int(args.attempt),
        **state.contract(),
    })
    print(
        f"[serving.worker {args.name}] ready on {args.host}:{port} "
        f"(pid {os.getpid()}, attempt {args.attempt}, "
        f"warmed {state.warmed})",
        file=sys.stderr, flush=True,
    )

    # accept loop: one parent connection at a time; a parent reconnect
    # (after its side of a torn stream) just lands back here
    srv.settimeout(0.25)
    rc = 0
    try:
        while not state.draining.is_set():
            state.maybe_follow()
            try:
                conn, _addr = srv.accept()
            except socket.timeout:
                continue
            with conn:
                conn.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                )
                conn.settimeout(0.25)
                bye = False
                while not state.draining.is_set() and not bye:
                    # between-messages = between-batches: the only place
                    # a followed update may apply (torn-read fence)
                    state.maybe_follow()
                    try:
                        msg = recv_msg(conn)
                    except socket.timeout:
                        continue
                    except TransportError:
                        break  # parent vanished; back to accept
                    if msg is None:
                        break  # clean disconnect
                    reply = state.handle(msg)
                    try:
                        send_msg(conn, reply)
                    except (TransportError, socket.timeout):
                        break  # parent gone or wedged; back to accept
                    if reply.get("kind") == "bye":
                        bye = True
                if bye:
                    return 0
    finally:
        try:
            srv.close()
        except OSError:
            pass
    if state.draining.is_set():
        rc = PREEMPTION_EXIT_CODE
    return rc


if __name__ == "__main__":
    sys.exit(worker_main())
