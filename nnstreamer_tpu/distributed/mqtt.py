"""Minimal MQTT 3.1.1 transport: client + in-process broker (stdlib only).

The reference's mqttsink/mqttsrc (``gst/mqtt/``) link against paho.mqtt.c;
this image has no MQTT library, so the TPU build carries its own small
implementation of the subset the elements need — QoS 0/1 publish (PUBACK +
DUP redelivery), subscribe with ``+``/``#`` wildcards, keep-alive pings,
automatic reconnect with re-subscribe — plus a localhost broker so
pipelines (and tests) run without external infrastructure.  Protocol per
the public OASIS MQTT 3.1.1 spec; reconnect semantics match the
reference's paho ``MQTTAsync`` usage (``gst/mqtt/mqttsrc.c`` reconnects
and resumes its subscription; ``mqttsink.h`` ``mqtt_qos``).

QoS 1 is at-least-once: a publish unacknowledged when the connection
drops is re-sent (DUP flag) after reconnect — receivers may see
duplicates, never corruption or silent loss.

This is control-plane-grade transport (sensor streams, events); bulk
tensor traffic between hosts should ride the gRPC query/edge elements.
"""

from __future__ import annotations

import socket
import struct
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from ..core.log import get_logger

log = get_logger("mqtt")

# packet types (MQTT 3.1.1 §2.2.1)
CONNECT, CONNACK = 1, 2
PUBLISH, PUBACK = 3, 4
SUBSCRIBE, SUBACK = 8, 9
UNSUBSCRIBE, UNSUBACK = 10, 11
PINGREQ, PINGRESP = 12, 13
DISCONNECT = 14


def _encode_len(n: int) -> bytes:
    out = b""
    while True:
        d = n % 128
        n //= 128
        out += bytes([d | (0x80 if n else 0)])
        if not n:
            return out


def _read_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("MQTT peer closed")
        buf += chunk
    return buf


def _read_packet(sock: socket.socket) -> Tuple[int, int, bytes]:
    head = _read_exact(sock, 1)[0]
    mult, length = 1, 0
    while True:
        b = _read_exact(sock, 1)[0]
        length += (b & 0x7F) * mult
        if not (b & 0x80):
            break
        mult *= 128
        if mult > 128**3:
            raise ConnectionError("malformed MQTT length")
    payload = _read_exact(sock, length) if length else b""
    return head >> 4, head & 0xF, payload


def _mqtt_str(s: str) -> bytes:
    b = s.encode()
    return struct.pack(">H", len(b)) + b


class MqttProtocolError(ValueError):
    pass


def _parse_publish(flags: int, body: bytes) -> Tuple[str, bytes, Optional[int]]:
    """PUBLISH variable header -> (topic, payload, packet_id|None); shared
    by broker and client so malformed-body handling stays in one place."""
    if len(body) < 2:
        raise MqttProtocolError("PUBLISH body too short")
    tlen = struct.unpack(">H", body[:2])[0]
    off = 2 + tlen
    if off > len(body):
        raise MqttProtocolError("PUBLISH topic length exceeds body")
    try:
        topic = body[2:off].decode()
    except UnicodeDecodeError as e:
        raise MqttProtocolError(f"PUBLISH topic not UTF-8: {e}") from None
    pid = None
    if (flags >> 1) & 0x3:  # QoS > 0 carries a packet id
        if off + 2 > len(body):
            raise MqttProtocolError("PUBLISH missing packet id")
        pid = struct.unpack(">H", body[off : off + 2])[0]
        off += 2
    return topic, body[off:], pid


def _publish_packet(topic: str, payload: bytes, retain: bool = False,
                    qos: int = 0, pid: int = 0, dup: bool = False) -> bytes:
    var = _mqtt_str(topic)
    if qos:
        var += struct.pack(">H", pid)
    var += payload
    head = (PUBLISH << 4) | (1 if retain else 0) | ((qos & 0x3) << 1)
    if dup:
        head |= 0x8
    return bytes([head]) + _encode_len(len(var)) + var


# persistent-session store across broker restarts, keyed by port (see
# MiniBroker.__init__/close).  Entries carry a timestamp: a successor
# only adopts FRESH state (a restart follows its crash within seconds) —
# stale entries would contaminate an unrelated broker when the OS reuses
# an ephemeral port — and stale entries are evicted on every touch so a
# long-lived process cannot accumulate dead backlogs.
_SESSION_STORE: Dict[int, Tuple[float, Dict[str, "_BrokerSession"]]] = {}
_SESSION_STORE_TTL_S = 300.0


def _session_store_evict_stale(now: Optional[float] = None) -> None:
    now = time.monotonic() if now is None else now
    for port in [p for p, (ts, _) in _SESSION_STORE.items()
                 if now - ts > _SESSION_STORE_TTL_S]:
        del _SESSION_STORE[port]


def topic_matches(pattern: str, topic: str) -> bool:
    """MQTT wildcard match: ``+`` one level, ``#`` rest (spec §4.7)."""
    pp, tp = pattern.split("/"), topic.split("/")
    for i, p in enumerate(pp):
        if p == "#":
            return True
        if i >= len(tp):
            return False
        if p != "+" and p != tp[i]:
            return False
    return len(pp) == len(tp)


class _BrokerSession:
    """Per-client-id broker state: subscriptions (pattern -> granted QoS),
    the live socket (None while offline), QoS-1 messages in flight to the
    subscriber, and — for persistent (clean_session=0) sessions — messages
    queued while offline."""

    __slots__ = ("cid", "clean", "subs", "sock", "inflight", "queue",
                 "next_pid", "dropped")

    QUEUE_LIMIT = 1024     # offline/overflow backlog bound per session
    INFLIGHT_LIMIT = 512   # unacked deliveries per connected subscriber

    def __init__(self, cid: str, clean: bool):
        self.cid = cid
        self.clean = clean
        self.subs: Dict[str, int] = {}
        self.sock: Optional[socket.socket] = None
        # pid -> [topic, payload, last_sent_ts, retain]
        self.inflight: Dict[int, list] = {}
        self.queue: List[Tuple[str, bytes, bool]] = []
        self.next_pid = 0
        self.dropped = 0

    def alloc_pid(self) -> int:
        # never reuse a pid that is still awaiting its PUBACK (wraparound
        # would silently overwrite an undelivered message); INFLIGHT_LIMIT
        # << 65535 keeps this loop trivially bounded
        while True:
            self.next_pid = (self.next_pid % 0xFFFF) + 1
            if self.next_pid not in self.inflight:
                return self.next_pid


class MiniBroker:
    """Tiny localhost MQTT broker: wildcards, retained messages, QoS 0/1
    end-to-end.  Subscriber-side QoS 1 honors the spec: the requested QoS
    is granted in SUBACK, deliveries carry packet ids and are retransmitted
    (DUP) until PUBACKed, and persistent sessions (CONNECT clean=0) keep
    subscriptions + undelivered QoS-1 messages across subscriber death so
    a reconnecting subscriber loses nothing (≙ paho/mosquitto behavior the
    reference relies on, gst/mqtt/mqttsink.h:77 ``mqtt_qos``)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 retransmit_s: float = 1.0):
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        # REUSEADDR (not REUSEPORT: two live brokers on one port would
        # silently load-balance clients between them) — restart rebinding
        # works because close() shuts every client sock down first, so the
        # old listener and its connections are gone before the new bind
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(16)
        self.host, self.port = self._srv.getsockname()
        self._lock = threading.Lock()
        self._sessions: Dict[str, _BrokerSession] = {}
        # broker "persistence": a rebind on the same port adopts the
        # previous instance's persistent sessions (subscriptions +
        # undelivered QoS-1 backlog), the in-process analog of
        # mosquitto's persistence file — without it, messages the broker
        # PUBACKed but had not yet delivered die with the process (the
        # at-least-once chain is only per-hop)
        _session_store_evict_stale()
        stored = _SESSION_STORE.pop(self.port, None)
        if stored is not None:
            ts, sessions = stored
            if time.monotonic() - ts <= _SESSION_STORE_TTL_S:
                self._sessions.update(sessions)
        self._by_sock: Dict[socket.socket, _BrokerSession] = {}
        # per-sock write locks so a publisher fan-out and the subscriber's
        # own control responses (SUBACK/PINGRESP/retained) cannot
        # interleave mid-sendall
        self._wlocks: Dict[socket.socket, threading.Lock] = {}
        self._retained: Dict[str, bytes] = {}
        self._retransmit_s = retransmit_s
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._accept_loop, name="mqtt-broker", daemon=True
        )
        self._thread.start()
        self._redeliver = threading.Thread(
            target=self._redeliver_loop, name="mqtt-broker-qos1", daemon=True
        )
        self._redeliver.start()

    def has_subscriber(self, topic: str) -> bool:
        """True when some LIVE session holds a subscription matching
        ``topic`` — the event-driven readiness signal tests use instead
        of sleeping an arbitrary margin after starting a subscriber."""
        with self._lock:
            return any(
                sess.sock is not None and topic_matches(pat, topic)
                for sess in self._sessions.values()
                for pat in sess.subs
            )

    def wait_subscriber(self, topic: str, timeout_s: float = 10.0) -> bool:
        """Block until :meth:`has_subscriber` (bounded); True on success."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.has_subscriber(topic):
                return True
            time.sleep(0.01)
        return False

    def close(self) -> None:
        self._stop.set()
        # persist BEFORE freeing the port: a successor binding the port
        # must never win the race against the store write (it would miss
        # the PUBACKed-but-undelivered backlog — the exact loss this
        # persistence exists to prevent)
        with self._lock:
            keep: Dict[str, _BrokerSession] = {}
            for cid, sess in self._sessions.items():
                if sess.clean:
                    continue
                sess.sock = None
                requeue = [(t, p, bool(r))
                           for t, p, _, r in sess.inflight.values()]
                sess.inflight = {}
                merged = requeue + sess.queue
                if len(merged) > sess.QUEUE_LIMIT:
                    sess.dropped += len(merged) - sess.QUEUE_LIMIT
                sess.queue = merged[: sess.QUEUE_LIMIT]
                keep[cid] = sess
            _session_store_evict_stale()
            if keep:
                _SESSION_STORE[self.port] = (time.monotonic(), keep)
        try:
            # shutdown wakes a thread blocked in accept() (plain close of
            # a listening fd can leave it blocked forever on Linux)
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._srv.close()
        except OSError:
            pass
        with self._lock:
            socks = list(self._by_sock)
        for s in socks:
            try:
                # shutdown BEFORE close: close() alone neither wakes a
                # thread blocked in recv on this fd nor guarantees a
                # prompt FIN to the peer; shutdown does both, so
                # clients detect broker death immediately
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass
        with self._lock:
            self._by_sock.clear()
            self._wlocks.clear()
            self._sessions.clear()

    # -- internals ----------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                sock, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(
                target=self._client_loop, args=(sock,), daemon=True
            ).start()

    @staticmethod
    def _parse_connect(body: bytes) -> Tuple[str, bool]:
        """CONNECT variable header + payload -> (client_id, clean_session).
        MQTT 3.1.1 §3.1: proto name str, level byte, flags byte,
        keepalive u16, then the client id string."""
        off = 2 + struct.unpack(">H", body[:2])[0]  # skip protocol name
        flags = body[off + 1]
        off += 4  # level + flags + keepalive
        cid_len = struct.unpack(">H", body[off : off + 2])[0]
        cid = body[off + 2 : off + 2 + cid_len].decode()
        return cid, bool(flags & 0x02)

    def _open_session(self, sock: socket.socket,
                      body: bytes) -> Tuple[_BrokerSession, bool]:
        cid, clean = self._parse_connect(body)
        with self._lock:
            existing = self._sessions.get(cid) if cid else None
            # a still-live connection under this client id is displaced
            # whatever the clean flag (MQTT 3.1.1 §3.1.4: new wins)
            old = existing.sock if existing is not None else None
            sess = existing if (existing is not None and not clean) else None
            present = sess is not None
            if sess is None:
                sess = _BrokerSession(cid or f"anon-{id(sock):x}", clean)
            sess.clean = clean
            sess.sock = sock
            self._sessions[sess.cid] = sess
            self._by_sock[sock] = sess
            self._wlocks[sock] = threading.Lock()
        if old is not None and old is not sock:
            try:
                old.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        return sess, present

    def _client_loop(self, sock: socket.socket) -> None:
        sess = None
        try:
            # bound SENDS only (SO_SNDTIMEO, not settimeout: recv must
            # stay blocking): a wedged subscriber whose TCP window filled
            # would otherwise stall the shared redelivery/fan-out threads
            # in sendall forever
            sock.setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDTIMEO,
                struct.pack("ll", 5, 0),
            )
            # bounded handshake: a peer that connects and never sends
            # CONNECT must not wedge this thread until process exit
            sock.settimeout(10.0)
            ptype, _, body = _read_packet(sock)
            if ptype != CONNECT:
                sock.close()
                return
            # allow-blocking: post-handshake reads are stream semantics
            # (clients ping on their own schedule); close() shutdown()s
            # every client socket, so the blocked recv has an escape
            sock.settimeout(None)
            sess, present = self._open_session(sock, body)
            sock.sendall(bytes([CONNACK << 4, 2, 1 if present else 0, 0]))
            if present:
                self._resume_delivery(sess)
            while not self._stop.is_set():
                ptype, flags, body = _read_packet(sock)
                if ptype == PUBLISH:
                    self._handle_publish(sock, flags, body)
                elif ptype == PUBACK:
                    if len(body) >= 2:
                        (pid,) = struct.unpack(">H", body[:2])
                        with self._lock:
                            sess.inflight.pop(pid, None)
                elif ptype == SUBSCRIBE:
                    self._handle_subscribe(sock, sess, body)
                elif ptype == UNSUBSCRIBE:
                    self._handle_unsubscribe(sock, sess, body)
                elif ptype == PINGREQ:
                    self._send(sock, bytes([PINGRESP << 4, 0]))
                elif ptype == DISCONNECT:
                    break
        except (ConnectionError, OSError):
            pass
        except (MqttProtocolError, struct.error, IndexError,
                UnicodeDecodeError) as e:
            log.warning("broker: dropping client on malformed packet: %s", e)
        finally:
            with self._lock:
                self._by_sock.pop(sock, None)
                self._wlocks.pop(sock, None)
                if sess is not None and sess.sock is sock:
                    sess.sock = None
                    # drop only OUR session entry: a reconnect may already
                    # have replaced this cid with a fresh session object
                    if sess.clean and self._sessions.get(sess.cid) is sess:
                        self._sessions.pop(sess.cid, None)
            try:
                sock.close()
            except OSError:
                pass

    def _resume_delivery(self, sess: _BrokerSession) -> None:
        """Persistent-session reconnect: retransmit unacked inflight
        (DUP) and flush the offline queue as fresh QoS-1 deliveries."""
        with self._lock:
            sock = sess.sock
            inflight = sorted(sess.inflight.items())
            queued, sess.queue = sess.queue, []
        if sock is None:
            return
        for pid, entry in inflight:
            self._send(sock, _publish_packet(
                entry[0], entry[1], entry[3], qos=1, pid=pid, dup=True))
            entry[2] = time.monotonic()
        for topic, payload, retain in queued:
            self._deliver_qos1(sess, topic, payload, retain)

    def _deliver_qos1(self, sess: _BrokerSession, topic: str,
                      payload: bytes, retain: bool = False) -> None:
        with self._lock:
            sock = sess.sock
            # offline subscriber — or a connected one that stopped acking
            # (inflight full): park in the bounded queue; the redelivery
            # loop promotes queued entries as PUBACKs free inflight room
            if sock is None or len(sess.inflight) >= sess.INFLIGHT_LIMIT:
                if len(sess.queue) < sess.QUEUE_LIMIT:
                    sess.queue.append((topic, payload, retain))
                else:
                    sess.dropped += 1
                return
            pid = sess.alloc_pid()
            sess.inflight[pid] = [topic, payload, time.monotonic(), retain]
        self._send(sock, _publish_packet(topic, payload, retain, 1, pid))

    def _redeliver_loop(self) -> None:
        """QoS-1 redelivery to subscribers: resend inflight entries older
        than the retransmit interval with DUP until PUBACKed, and promote
        queued messages into freed inflight slots."""
        while not self._stop.wait(max(0.05, self._retransmit_s / 2)):
            now = time.monotonic()
            with self._lock:
                stale = [
                    (sess.sock, pid, e)
                    for sess in self._sessions.values() if sess.sock
                    for pid, e in sess.inflight.items()
                    if now - e[2] >= self._retransmit_s
                ]
                promotable = [
                    sess for sess in self._sessions.values()
                    if sess.sock and sess.queue
                    and len(sess.inflight) < sess.INFLIGHT_LIMIT
                ]
            for sock, pid, entry in stale:
                entry[2] = now
                self._send(sock, _publish_packet(
                    entry[0], entry[1], entry[3], qos=1, pid=pid, dup=True))
            for sess in promotable:
                with self._lock:
                    room = sess.INFLIGHT_LIMIT - len(sess.inflight)
                    batch, sess.queue = (
                        sess.queue[:room], sess.queue[room:])
                for topic, payload, retain in batch:
                    self._deliver_qos1(sess, topic, payload, retain)

    def _handle_publish(self, sock: socket.socket, flags: int,
                        body: bytes) -> None:
        topic, payload, pid = _parse_publish(flags, body)
        pub_qos = (flags >> 1) & 0x3
        if flags & 0x1:  # retain; empty payload DELETES (MQTT 3.1.1 §3.3.1.3)
            with self._lock:
                if payload:
                    self._retained[topic] = payload
                else:
                    self._retained.pop(topic, None)
        # fan out at min(publish QoS, granted subscription QoS) per
        # subscriber (MQTT 3.1.1 §3.8.4)
        with self._lock:
            targets = [
                (sess, max(
                    (q for p, q in sess.subs.items()
                     if topic_matches(p, topic)), default=-1,
                ))
                for sess in self._sessions.values()
            ]
        qos0_packet = None
        for sess, sub_qos in targets:
            if sub_qos < 0:
                continue
            if min(pub_qos, sub_qos) >= 1:
                self._deliver_qos1(sess, topic, payload)
            elif sess.sock is not None:
                if qos0_packet is None:
                    qos0_packet = _publish_packet(topic, payload)
                self._send(sess.sock, qos0_packet)
        if pid is not None:
            # QoS 1 in: acknowledge the publisher only AFTER the message
            # is enqueued/tracked for every matching subscriber — an ack
            # before fan-out leaves a crash window where an acked message
            # exists nowhere (found by the 20-min soak: 3 of 57k frames
            # lost across 9 broker kills)
            self._send(sock, bytes([PUBACK << 4, 2]) + struct.pack(">H", pid))

    def _send(self, sock: socket.socket, data: bytes) -> None:
        with self._lock:
            wl = self._wlocks.get(sock)
        if wl is None:
            return
        try:
            with wl:
                sock.sendall(data)
        except socket.timeout:
            # send window stayed full for the whole SNDTIMEO: the peer is
            # wedged — tear it down so its session goes offline (messages
            # queue) instead of letting it stall shared delivery threads
            log.warning("broker: peer stopped reading; disconnecting it")
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        except OSError:
            pass

    def _handle_subscribe(self, sock: socket.socket, sess: _BrokerSession,
                          body: bytes) -> None:
        pid = body[:2]
        off = 2
        grants = []
        new_pats = []
        with self._lock:
            while off < len(body):
                ln = struct.unpack(">H", body[off : off + 2])[0]
                pat = body[off + 2 : off + 2 + ln].decode()
                req_qos = body[off + 2 + ln] & 0x3
                granted = min(req_qos, 1)  # QoS 2 not implemented
                sess.subs[pat] = granted  # re-subscribe replaces
                grants.append(granted)
                new_pats.append(pat)
                off += 2 + ln + 1
            retained = [
                (t, p, max(
                    (sess.subs[pat] for pat in new_pats
                     if topic_matches(pat, t)), default=0,
                ))
                for t, p in self._retained.items()
                if any(topic_matches(pat, t) for pat in new_pats)
            ]
        self._send(
            sock,
            bytes([SUBACK << 4]) + _encode_len(2 + len(grants)) + pid
            + bytes(grants),
        )
        # retained state rides at the granted QoS (§3.3.1.3): a qos-1
        # subscription gets tracked, retransmitted retained delivery
        for t, p, q in retained:
            if q >= 1:
                self._deliver_qos1(sess, t, p, retain=True)
            else:
                self._send(sock, _publish_packet(t, p, retain=True))

    def _handle_unsubscribe(self, sock: socket.socket, sess: _BrokerSession,
                            body: bytes) -> None:
        pid = body[:2]
        off = 2
        with self._lock:
            while off < len(body):
                ln = struct.unpack(">H", body[off : off + 2])[0]
                pat = body[off + 2 : off + 2 + ln].decode()
                sess.subs.pop(pat, None)
                off += 2 + ln
        self._send(sock, bytes([UNSUBACK << 4, 2]) + pid)


class MqttClient:
    """MQTT 3.1.1 client: QoS 0/1 publish, subscribe(callback), automatic
    reconnect with re-subscribe and QoS-1 redelivery.

    ≙ the reference's paho ``MQTTAsync`` usage: ``mqtt_qos``
    (``gst/mqtt/mqttsink.h:77``) and mqttsrc's reconnect-and-resume."""

    def __init__(self, host: str, port: int, client_id: str = "",
                 keepalive: int = 60, timeout: float = 10.0,
                 reconnect: bool = True, retransmit_s: float = 2.0,
                 reconnect_delay_s: float = 0.1,
                 clean_session: bool = True,
                 brokers: Optional[Iterable[Tuple[str, int]]] = None):
        self._host, self._port, self._timeout = host, port, timeout
        # ordered failover list: (host, port) first, extras after.  The
        # reconnect loop dials each in turn per failed attempt, so a dead
        # primary fails over within one dial timeout — clients never need
        # to know which broker of the set is the live one.
        self._brokers: List[Tuple[str, int]] = [(host, int(port))]
        for h, p in (brokers or ()):
            if (h, int(p)) not in self._brokers:
                self._brokers.append((h, int(p)))
        self._broker_i = 0
        self._cid = client_id or f"nns-tpu-{id(self) & 0xFFFFFF:x}"
        # clean_session=False + a stable client_id = persistent session:
        # the broker keeps subscriptions and queues/retransmits QoS-1
        # deliveries across this client's death (at-least-once end-to-end)
        self._clean_session = clean_session
        self._keepalive = max(1, keepalive)
        self._reconnect_enabled = reconnect
        self._retransmit_s = retransmit_s
        # initial reconnect backoff (≙ paho MQTTAsync_setReconnectDelay):
        # publishers should use a LARGER delay than subscribers so that
        # after a broker restart the subscriptions are back before QoS-1
        # redelivery lands (a broker with no session persistence acks a
        # publish even when nobody is subscribed yet)
        self._reconnect_delay_s = max(0.05, reconnect_delay_s)
        self._wlock = threading.Lock()
        # per-pattern callbacks: a second subscribe() must not reroute
        # earlier patterns' messages to the newest callback
        self._subs: Dict[str, Callable[[str, bytes], None]] = {}
        self._sub_qos: Dict[str, int] = {}
        self._stop = threading.Event()
        self._pid_lock = threading.Lock()
        self._pid = 0
        # QoS-1 in flight: pid -> [topic, payload, retain, last_sent_ts]
        self._pending: Dict[int, list] = {}
        self._pending_lock = threading.Lock()
        self.connected = threading.Event()
        # connection-plane accounting (exact): successful reconnects and
        # retained QoS-1 publishes superseded while the broker was away
        self.reconnects = 0
        self.coalesced = 0
        self._on_connect: List[Callable[[], None]] = []
        self._sock: Optional[socket.socket] = None
        # first connect walks the failover list too: a dead primary with
        # a live standby must not fail construction.  Raises only when
        # EVERY broker refused.
        err: Optional[OSError] = None
        for i in range(len(self._brokers)):
            self._broker_i = i
            try:
                self._connect()
                err = None
                break
            except OSError as e:
                err = e
        if err is not None:
            raise err
        self._reader = threading.Thread(
            target=self._read_loop, name="mqtt-client", daemon=True
        )
        self._reader.start()
        # keepalive: a broker may drop us after 1.5x the advertised interval
        # with no inbound packets (MQTT 3.1.1 §3.1.2.10), so ping on a
        # timer; the same timer drives QoS-1 retransmission
        self._pinger = threading.Thread(
            target=self._ping_loop, name="mqtt-ping", daemon=True
        )
        self._pinger.start()

    # -- connection ---------------------------------------------------------
    @property
    def broker(self) -> Tuple[str, int]:
        """The (host, port) this client last connected (or dialed) to."""
        return self._brokers[self._broker_i]

    def on_connect(self, cb: Callable[[], None]) -> None:
        """Register a callback fired (from the reader thread) after every
        successful RE-connect, once the session is resumed — the hook an
        :class:`~..distributed.hybrid.Announcement` uses to re-publish its
        retained state into a restarted (amnesiac) or failed-over broker."""
        self._on_connect.append(cb)

    def _connect(self, reconnect: bool = False) -> None:
        self._host, self._port = self._brokers[self._broker_i]
        sock = socket.create_connection(
            (self._host, self._port), timeout=self._timeout
        )
        var = (
            _mqtt_str("MQTT") + bytes([4])  # protocol level 4 = 3.1.1
            + bytes([0x02 if self._clean_session else 0x00])
            + struct.pack(">H", self._keepalive)
            + _mqtt_str(self._cid)
        )
        sock.sendall(bytes([CONNECT << 4]) + _encode_len(len(var)) + var)
        ptype, _, body = _read_packet(sock)
        if ptype != CONNACK or body[1] != 0:
            sock.close()
            raise ConnectionError(f"MQTT connect refused: {body!r}")
        # bounded read: the ping loop elicits a PINGRESP well inside
        # every keepalive window, so a silent link for 1.5x keepalive
        # means the broker is gone — the reader's timeout then lands in
        # its (ConnectionError, OSError) handler and reconnects, instead
        # of blocking forever on a black-holed connection
        sock.settimeout(max(1.0, self._keepalive * 1.5))
        with self._wlock:
            self._sock = sock
        if reconnect:
            # counted BEFORE the event: whoever `connected` wakes reads an
            # exact count
            self.reconnects += 1
        self.connected.set()

    def _resume_session(self) -> None:
        """After reconnect: re-subscribe every pattern (clean-session
        broker forgot them) and re-send unacked QoS-1 publishes (DUP)."""
        for pattern in list(self._subs):
            try:
                self._send_subscribe(pattern)
            except OSError:
                return
        with self._pending_lock:
            pending = sorted(self._pending.items())
        for pid, entry in pending:
            topic, payload, retain, _ = entry
            try:
                self._send(_publish_packet(
                    topic, payload, retain, qos=1, pid=pid, dup=True
                ))
                entry[3] = time.monotonic()
            except OSError:
                return

    def _reconnect_loop(self) -> None:
        backoff = self._reconnect_delay_s
        self._stop.wait(self._reconnect_delay_s)
        while not self._stop.is_set():
            try:
                self._connect(reconnect=True)
                log.info("mqtt client reconnected to %s:%d",
                         self._host, self._port)
                self._resume_session()
                for cb in list(self._on_connect):
                    try:
                        cb()
                    except Exception:  # hook bugs must not kill the reader
                        log.exception("mqtt on_connect hook failed")
                return
            except OSError:
                # failover: advance to the next broker in the ordered list
                # before the next dial; back off only after a full cycle
                # of the list has been refused, so a live standby broker
                # is reached within one dial per dead predecessor
                self._broker_i = (self._broker_i + 1) % len(self._brokers)
                if self._broker_i == 0:
                    self._stop.wait(backoff)
                    backoff = min(backoff * 2, 2.0)

    # -- io -----------------------------------------------------------------
    def _send(self, data: bytes) -> None:
        with self._wlock:
            if self._sock is None:
                raise OSError("mqtt client not connected")
            self._sock.sendall(data)

    def _ping_loop(self) -> None:
        interval = min(self._keepalive / 2.0, max(self._retransmit_s, 0.2))
        while not self._stop.wait(interval):
            now = time.monotonic()
            with self._pending_lock:
                stale = [
                    (pid, e) for pid, e in sorted(self._pending.items())
                    if now - e[3] >= self._retransmit_s
                ]
            for pid, entry in stale:  # QoS-1 redelivery
                try:
                    self._send(_publish_packet(
                        entry[0], entry[1], entry[2], qos=1, pid=pid, dup=True
                    ))
                    entry[3] = now
                except OSError:
                    break
            try:
                self.ping()
            except OSError:
                continue  # reader notices and reconnects

    def _next_pid(self) -> int:
        with self._pid_lock:
            self._pid = (self._pid % 0xFFFF) + 1
            return self._pid

    # -- API ----------------------------------------------------------------
    def publish(self, topic: str, payload: bytes, retain: bool = False,
                qos: int = 0) -> None:
        if qos not in (0, 1):
            raise ValueError("only QoS 0/1 supported")
        if qos == 1:
            pid = self._next_pid()
            with self._pending_lock:
                if retain:
                    # retained semantics are last-writer-wins: a newer
                    # retained publish on the same topic supersedes any
                    # still-unacked one, so the outage backlog is bounded
                    # at ONE entry per retained topic and a reconnect
                    # never replays a stale announce/digest over a fresh
                    # one (subscribers additionally dedupe by seq)
                    for old_pid in [
                        p for p, e in self._pending.items()
                        if e[2] and e[0] == topic
                    ]:
                        del self._pending[old_pid]
                        self.coalesced += 1
                self._pending[pid] = [topic, payload, retain, time.monotonic()]
            try:
                self._send(_publish_packet(topic, payload, retain, 1, pid))
            except OSError:
                if not self._reconnect_enabled:
                    with self._pending_lock:
                        self._pending.pop(pid, None)
                    raise
                # stays pending; redelivered after reconnect
            return
        try:
            self._send(_publish_packet(topic, payload, retain))
        except OSError:
            if not self._reconnect_enabled:
                raise
            # fire-and-forget during the reconnect window: QoS 0 has no
            # delivery guarantee — dropping beats killing the pipeline
            log.debug("QoS-0 publish dropped while reconnecting")

    def unacked(self) -> int:
        """Outstanding QoS-1 publishes (0 = everything acknowledged)."""
        with self._pending_lock:
            return len(self._pending)

    def drain(self, timeout_s: float = 5.0) -> int:
        """Wait up to `timeout_s` for all QoS-1 publishes to be PUBACKed;
        returns how many remain unacknowledged (0 = clean)."""
        deadline = time.monotonic() + timeout_s
        while self.unacked() and time.monotonic() < deadline:
            time.sleep(0.05)
        return self.unacked()

    def _send_subscribe(self, pattern: str) -> None:
        var = (
            struct.pack(">H", self._next_pid()) + _mqtt_str(pattern)
            + bytes([self._sub_qos.get(pattern, 0)])
        )
        self._send(bytes([(SUBSCRIBE << 4) | 0x2]) + _encode_len(len(var)) + var)

    def subscribe(self, pattern: str,
                  callback: Callable[[str, bytes], None],
                  qos: int = 0) -> None:
        if qos not in (0, 1):
            raise ValueError("only QoS 0/1 supported")
        self._subs[pattern] = callback
        self._sub_qos[pattern] = qos
        try:
            self._send_subscribe(pattern)
        except OSError:
            if not self._reconnect_enabled:
                raise
            # recorded; _resume_session re-sends it after reconnect

    def ping(self) -> None:
        self._send(bytes([PINGREQ << 4, 0]))

    def close(self) -> None:
        self._stop.set()
        try:
            self._send(bytes([DISCONNECT << 4, 0]))
        except OSError:
            pass
        with self._wlock:
            if self._sock is not None:
                try:  # wake the reader blocked in recv (see MiniBroker.close)
                    self._sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None

    # -- reader -------------------------------------------------------------
    def _read_loop(self) -> None:
        while not self._stop.is_set():
            sock = self._sock
            if sock is None:
                return
            try:
                ptype, flags, body = _read_packet(sock)
            except (ConnectionError, OSError):
                self.connected.clear()
                try:  # release the dead fd (one leak per reconnect otherwise)
                    sock.close()
                except OSError:
                    pass
                if self._stop.is_set() or not self._reconnect_enabled:
                    return
                self._reconnect_loop()
                continue
            if ptype == PUBACK and len(body) >= 2:
                (pid,) = struct.unpack(">H", body[:2])
                with self._pending_lock:
                    self._pending.pop(pid, None)
                continue
            if ptype != PUBLISH or not self._subs:
                continue
            try:
                topic, payload, pid = _parse_publish(flags, body)
            except MqttProtocolError as e:
                log.warning("client: dropping malformed PUBLISH: %s", e)
                continue
            if pid is not None:  # QoS-1 inbound: acknowledge
                try:
                    self._send(
                        bytes([PUBACK << 4, 2]) + struct.pack(">H", pid)
                    )
                except OSError:
                    pass
            for pattern, cb in list(self._subs.items()):
                if not topic_matches(pattern, topic):
                    continue
                try:
                    cb(topic, payload)
                except Exception:  # subscriber bugs must not kill the reader
                    log.exception("mqtt subscribe callback failed")
