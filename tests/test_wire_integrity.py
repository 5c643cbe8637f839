"""Data-plane integrity (ISSUE 4): wire envelope v2 checksums, typed
bounded decode, version negotiation, corruption fault injection and the
client/server corruption semantics, crash-atomic datarepo, fuzz smoke.

Acceptance contract (Documentation/wire-protocol.md):
* every malformed input raises a typed WireError subclass — truncation
  at every field boundary, oversize declared lengths, bad magic/version/
  count each pin to WireTruncationError/WireCorruptionError;
* servers answer corrupt requests with 'C' (tcp) / DATA_LOSS (grpc) and
  stay alive; clients count corruption_detected, retry resend-safe, and
  sustained corruption trips the breaker while one blip does not;
* a v2 client round-trips against a v1-framed peer (negotiation);
* tools/fuzz_wire.py runs >= 10k seeded mutations with zero uncaught
  exceptions, hangs, or over-MAX_BODY allocations.
"""

import json
import os
import socket
import struct
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from nnstreamer_tpu.core.buffer import TensorFrame
from nnstreamer_tpu.core.resilience import FAULTS, is_transient
from nnstreamer_tpu.distributed import tcp_query, wire
from nnstreamer_tpu.distributed.wire import (
    WireCorruptionError,
    WireError,
    WireTruncationError,
)
from nnstreamer_tpu.pipeline import parse_pipeline


@pytest.fixture(autouse=True)
def _reset_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def frame(v=1.0, n=4):
    return TensorFrame([np.full((n,), v, np.float32)], pts=0.5,
                       meta={"tag": "t"})


# ---------------------------------------------------------------------------
# envelope round trips + version knobs
# ---------------------------------------------------------------------------
class TestEnvelopeRoundtrip:
    @pytest.mark.parametrize("version", [1, 2])
    def test_roundtrip_preserves_everything(self, version):
        f = TensorFrame(
            [np.arange(12, dtype=np.float32).reshape(3, 4),
             np.uint8([[1], [2]])],
            pts=1.25, meta={"k": "v", "n": [1, 2]})
        f.seq = 42
        g = wire.decode_frame(wire.encode_frame(f, version=version))
        assert wire.frame_version(wire.encode_frame(f, version=version)) == version
        np.testing.assert_array_equal(g.tensors[0], f.tensors[0])
        np.testing.assert_array_equal(g.tensors[1], f.tensors[1])
        assert g.pts == 1.25 and g.seq == 42 and g.meta["k"] == "v"

    def test_v2_is_default_and_v1_still_decodes(self):
        assert wire.frame_version(wire.encode_frame(frame())) == 2
        g = wire.decode_frame(wire.encode_frame(frame(3.0), version=1))
        assert float(g.tensors[0][0]) == 3.0

    def test_env_knob_pins_v1(self, monkeypatch):
        monkeypatch.setenv("NNS_WIRE_V", "1")
        assert wire.default_version() == 1
        assert wire.frame_version(wire.encode_frame(frame())) == 1
        monkeypatch.delenv("NNS_WIRE_V")
        assert wire.default_version() == 2

    def test_bitflip_detected_everywhere_in_v2(self):
        buf = bytearray(wire.encode_frame(frame()))
        # flip one bit at a spread of positions: header, meta, payload
        for pos in (6, 25, len(buf) // 2, len(buf) - 1):
            bad = bytearray(buf)
            bad[pos] ^= 0x10
            with pytest.raises(WireCorruptionError):
                wire.decode_frame(bad)

    def test_verify_off_skips_crc(self):
        bad = bytearray(wire.encode_frame(frame()))
        bad[-1] ^= 1  # payload corruption only
        g = wire.decode_frame(bad, verify=False)  # garbage-tolerant debug mode
        assert g.tensors[0].shape == (4,)

    @pytest.mark.parametrize("version", [1, 2])
    def test_batch_roundtrip(self, version):
        frames = [frame(i) for i in range(3)]
        out = wire.decode_frames(wire.encode_frames(frames, version=version))
        assert [float(f.tensors[0][0]) for f in out] == [0.0, 1.0, 2.0]

    def test_batch_skeleton_crc_verified(self):
        buf = bytearray(wire.encode_frames([frame(1), frame(2)]))
        # a flipped bit in the crc field itself: structure walks clean,
        # the skeleton checksum is what refuses it
        bad = bytearray(buf)
        bad[6] ^= 1  # crc field of the 'NNSC' header
        with pytest.raises(WireCorruptionError, match="batch checksum"):
            wire.decode_frames(bad)
        # a flipped bit in a length prefix is caught typed too (bounds
        # walk or checksum, whichever fires first)
        bad = bytearray(buf)
        bad[_b2head_size()] ^= 1
        with pytest.raises(WireError):
            wire.decode_frames(bad)

    def test_is_batch_payload_both_magics(self):
        assert wire.is_batch_payload(wire.encode_frames([frame()], version=1))
        assert wire.is_batch_payload(wire.encode_frames([frame()], version=2))
        assert not wire.is_batch_payload(wire.encode_frame(frame()))


def _b2head_size():
    return struct.calcsize("<IHI")


# ---------------------------------------------------------------------------
# malformed-input truth table (satellite): every case pinned to its type
# ---------------------------------------------------------------------------
class TestMalformedTruthTable:
    def _boundaries(self, buf):
        sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
        try:
            import fuzz_wire
        finally:
            sys.path.pop(0)
        return fuzz_wire._walk_frame_boundaries(bytes(buf))

    def test_v1_truncation_at_every_field_boundary(self):
        buf = wire.encode_frame(frame(), version=1)
        for cut in self._boundaries(buf):
            if cut == len(buf):
                continue
            with pytest.raises(WireTruncationError):
                wire.decode_frame(buf[:cut])

    def test_v2_truncation_with_verify_reads_as_corruption(self):
        # the checksum pass sees any truncated v2 buffer first
        buf = wire.encode_frame(frame(), version=2)
        with pytest.raises(WireCorruptionError):
            wire.decode_frame(buf[: len(buf) - 3])
        # sub-header cuts can't even reach the crc: truncation
        with pytest.raises(WireTruncationError):
            wire.decode_frame(buf[:10])
        # with verification off the classification is structural again
        for cut in self._boundaries(buf):
            if cut == len(buf):
                continue
            with pytest.raises(WireTruncationError):
                wire.decode_frame(buf[:cut], verify=False)

    def test_empty_and_bad_magic(self):
        with pytest.raises(WireTruncationError):
            wire.decode_frame(b"")
        with pytest.raises(WireCorruptionError):
            wire.decode_frame(b"XXXXXXXXXX" + b"\0" * 30)

    def test_unsupported_version(self):
        # a flipped bit INSIDE the version field evades the CRC (it
        # selects which header to verify), so this must classify as
        # corruption — typed and transient like every other case
        buf = bytearray(wire.encode_frame(frame(), version=1))
        struct.pack_into("<H", buf, 4, 7)
        with pytest.raises(WireCorruptionError,
                           match="unsupported wire version"):
            wire.decode_frame(buf)
        try:
            wire.decode_frame(buf)
        except WireError as e:
            assert is_transient(e)

    def test_meta_len_hostile(self):
        v1 = bytearray(wire.encode_frame(frame(), version=1))
        # implausibly huge -> corruption BEFORE any allocation
        struct.pack_into("<I", v1, 22, 0xFFFFFFFF)
        with pytest.raises(WireCorruptionError, match="implausible meta"):
            wire.decode_frame(v1)
        # plausible but past the buffer -> truncation
        struct.pack_into("<I", v1, 22, len(v1) + 100)
        with pytest.raises(WireTruncationError):
            wire.decode_frame(v1)

    def test_meta_not_json_or_not_object(self):
        f = TensorFrame([np.float32([1.0])], meta={})
        buf = bytearray(wire.encode_frame(f, version=1))
        # meta is b"{}" at offset 26: overwrite with junk / a JSON array
        assert bytes(buf[26:28]) == b"{}"
        buf[26:28] = b"\xff\xfe"
        with pytest.raises(WireCorruptionError, match="meta"):
            wire.decode_frame(buf)
        buf[26:28] = b"[]"
        with pytest.raises(WireCorruptionError, match="not a JSON object"):
            wire.decode_frame(buf)

    def test_tensor_count_hostile(self):
        buf = bytearray(wire.encode_frame(frame(), version=1))
        meta_len = struct.unpack_from("<I", buf, 22)[0]
        nt_off = 26 + meta_len
        struct.pack_into("<H", buf, nt_off, 60000)  # over TENSOR_COUNT_LIMIT
        with pytest.raises(WireCorruptionError, match="tensor count"):
            wire.decode_frame(buf)
        struct.pack_into("<H", buf, nt_off, 3)  # plausible, data for 1
        with pytest.raises(WireTruncationError):
            wire.decode_frame(buf)

    def test_payload_len_contradicts_header(self):
        buf = bytearray(wire.encode_frame(frame(), version=1))
        # payload_len is the u64 right before the 16-byte payload
        off = len(buf) - 16 - 8
        struct.pack_into("<Q", buf, off, 2**62)
        with pytest.raises(WireCorruptionError, match="contradicts"):
            wire.decode_frame(buf)

    def test_bad_flex_dtype_is_corruption(self):
        buf = bytearray(wire.encode_frame(frame(), version=1))
        idx = bytes(buf).find(b"float32")
        buf[idx : idx + 7] = b"flort32"
        with pytest.raises(WireCorruptionError):
            wire.decode_frame(buf)

    def test_trailing_garbage_rejected(self):
        buf = wire.encode_frame(frame(), version=1) + b"\x00\x01"
        with pytest.raises(WireCorruptionError, match="trailing"):
            wire.decode_frame(buf)

    def test_batch_truth_table(self):
        frames = [frame(1), frame(2)]
        v1 = bytearray(wire.encode_frames(frames, version=1))
        with pytest.raises(WireCorruptionError, match="batch magic"):
            wire.decode_frames(b"XXXX" + bytes(v1[4:]))
        # count says 3, data holds 2 -> truncation
        bad = bytearray(v1)
        struct.pack_into("<H", bad, 4, 3)
        with pytest.raises(WireTruncationError):
            wire.decode_frames(bad)
        # entry length beyond MAX_BODY -> corruption before allocation
        bad = bytearray(v1)
        struct.pack_into("<Q", bad, 6, wire.MAX_BODY + 1)
        with pytest.raises(WireCorruptionError, match="cap"):
            wire.decode_frames(bad)
        # entry length beyond the buffer -> truncation
        bad = bytearray(v1)
        struct.pack_into("<Q", bad, 6, len(v1))
        with pytest.raises(WireTruncationError):
            wire.decode_frames(bad)
        # trailing bytes -> corruption
        with pytest.raises(WireCorruptionError, match="trailing"):
            wire.decode_frames(bytes(v1) + b"\x00")

    def test_typed_errors_are_transient_valueerrors(self):
        for exc in (WireCorruptionError("x"), WireTruncationError("x")):
            assert isinstance(exc, WireError)
            assert isinstance(exc, ValueError)
            assert is_transient(exc)  # nns_transient marker wins


# ---------------------------------------------------------------------------
# tcp_query message framing: parse truth table + crc
# ---------------------------------------------------------------------------
class TestTcpMessageFraming:
    @pytest.mark.parametrize("version", [1, 2])
    def test_roundtrip(self, version):
        body = wire.encode_frame(frame(), version=version)
        msg = tcp_query.encode_msg(ord("Q"), body, 2.5, version=version)
        mtype, got, deadline = tcp_query.parse_msg(msg, version=version)
        assert mtype == ord("Q") and deadline == 2.5
        assert bytes(got) == body

    def test_header_truncation(self):
        msg = tcp_query.encode_msg(ord("Q"), b"abc", version=2)
        for cut in (0, 5, 12, 20):
            with pytest.raises(WireTruncationError):
                tcp_query.parse_msg(msg[:cut], version=2)

    def test_body_truncation(self):
        msg = tcp_query.encode_msg(ord("Q"), b"abcdef", version=1)
        with pytest.raises(WireTruncationError):
            tcp_query.parse_msg(msg[:-2], version=1)

    def test_oversize_declared_body(self):
        head = struct.pack("<BQd", ord("Q"), wire.MAX_BODY + 1, 0.0)
        with pytest.raises(WireCorruptionError, match="exceeds"):
            tcp_query.parse_msg(head, version=1)

    def test_v2_crc_mismatch_and_verify_off(self):
        msg = bytearray(tcp_query.encode_msg(ord("Q"), b"abcdef", version=2))
        msg[-1] ^= 1
        with pytest.raises(WireCorruptionError, match="message checksum"):
            tcp_query.parse_msg(msg, version=2)
        mtype, body, _ = tcp_query.parse_msg(msg, version=2, verify=False)
        assert bytes(body) == b"abcde\x67"


# ---------------------------------------------------------------------------
# FaultInjector corrupt= kind
# ---------------------------------------------------------------------------
class TestCorruptFaults:
    def test_deterministic_bitflip(self):
        data = bytes(range(64))
        FAULTS.arm("site", corrupt="bitflip", every=1, seed=5)
        a = FAULTS.mangle("site", data)
        FAULTS.arm("site", corrupt="bitflip", every=1, seed=5)
        b = FAULTS.mangle("site", data)
        assert a == b != data
        assert len(a) == len(data)
        # exactly one bit differs
        diff = [x ^ y for x, y in zip(a, data)]
        assert sum(bin(d).count("1") for d in diff) == 1

    def test_truncate_kind_and_stats(self):
        data = bytes(range(64))
        FAULTS.arm("site", corrupt="truncate", every=2, seed=5)
        outs = [FAULTS.mangle("site", data) for _ in range(4)]
        assert len(outs[0]) < 64 and outs[1] == data
        assert len(outs[2]) < 64 and outs[3] == data
        assert FAULTS.stats("site") == {"calls": 4, "fired": 2}

    def test_check_ignores_corrupt_plans(self):
        FAULTS.arm("site", corrupt="bitflip", every=1)
        FAULTS.check("site")  # must not raise, must not consume
        assert FAULTS.stats("site")["calls"] == 0

    def test_unarmed_site_passthrough(self):
        data = b"hello"
        assert FAULTS.mangle("nope", data) is data
        FAULTS.arm("other", exc=ValueError)
        assert FAULTS.mangle("nope", data) is data  # raise plan elsewhere

    def test_bad_kind_rejected(self):
        with pytest.raises(ValueError, match="bitflip|truncate"):
            FAULTS.arm("site", corrupt="scramble")

    def test_mangle_parts_joins_only_when_armed(self):
        parts = [b"ab", memoryview(b"cd")]
        assert FAULTS.mangle_parts("site", parts) is parts
        FAULTS.arm("site", corrupt="bitflip", every=1, seed=1)
        (out,) = FAULTS.mangle_parts("site", parts)
        assert len(out) == 4 and out != b"abcd"


# ---------------------------------------------------------------------------
# client corruption semantics (unit, fake connections)
# ---------------------------------------------------------------------------
class TestCorruptClientUnit:
    def make_client(self, corrupt_retries=2, breaker_threshold=3):
        from nnstreamer_tpu.elements.query import TensorQueryClient, _PoolState

        q = TensorQueryClient("q")
        q.set_property("corrupt-retries", corrupt_retries)
        q.set_property("breaker-threshold", breaker_threshold)
        q.set_property("retries", 0)
        q.set_property("retry-backoff", 0.0)
        return q, _PoolState

    def test_single_corruption_retried_no_breaker_trip(self):
        q, _PoolState = self.make_client()

        class CorruptOnce:
            addr = "fake:1"
            calls = 0

            def invoke(self, f, timeout):
                type(self).calls += 1
                if type(self).calls == 1:
                    raise WireCorruptionError("frame checksum mismatch")
                return f

        q._pstate = _PoolState((CorruptOnce(),), (("fake", 1),), 0)
        q._stopped = False
        f = frame(7.0)
        assert q._invoke_failover(f, 0) is f
        h = q.health_info()
        assert h["corruption_detected"] == 1
        assert h["retried"] == 1 and h["delivered"] == 1
        snap = h["breakers"]["fake:1"]
        # ONE corrupt reply is recorded but never trips the breaker
        assert snap["state"] == "closed" and snap["trips"] == 0
        assert snap["recent_failures"] == 0  # cleared by the success

    def test_sustained_corruption_trips_breaker(self):
        q, _PoolState = self.make_client(corrupt_retries=3,
                                         breaker_threshold=2)

        class AlwaysCorrupt:
            addr = "fake:1"

            def invoke(self, f, timeout):
                raise WireCorruptionError("frame checksum mismatch")

        q._pstate = _PoolState((AlwaysCorrupt(),), (("fake", 1),), 0)
        q._stopped = False
        with pytest.raises(WireCorruptionError):
            q._invoke_failover(frame(), 0)
        h = q.health_info()
        assert h["corruption_detected"] >= 2
        assert h["breakers"]["fake:1"]["trips"] >= 1
        assert h["delivered"] == 0


# ---------------------------------------------------------------------------
# negotiation: v2 client <-> v1 peer, both transports of the claim
# ---------------------------------------------------------------------------
class EchoCore:
    """Minimal stand-in core for transport-level tests."""

    corrupt_requests = 0

    def check_caps(self, caps):
        return caps

    def process(self, frames, timeout):
        return [TensorFrame([np.asarray(t) * 2 for t in f.tensors])
                for f in frames]


class TestNegotiation:
    def test_v2_client_v1_server_roundtrip(self):
        srv = tcp_query.TcpQueryServer(EchoCore(), port=0, wire_version=1)
        srv.start()
        try:
            conn = tcp_query.TcpQueryConnection("127.0.0.1", srv.port,
                                                timeout=5)
            try:
                out = conn.invoke(frame(3.0))
                assert float(out.tensors[0][0]) == 6.0
                outs = conn.invoke_batch([frame(1.0), frame(2.0)])
                assert [float(o.tensors[0][0]) for o in outs] == [2.0, 4.0]
                assert conn._peer_v1  # learned the peer speaks v1
                assert set(conn._sock_ver.values()) <= {1}
            finally:
                conn.close()
        finally:
            srv.stop()

    def test_v1_client_v2_server_roundtrip(self):
        srv = tcp_query.TcpQueryServer(EchoCore(), port=0)
        srv.start()
        try:
            conn = tcp_query.TcpQueryConnection("127.0.0.1", srv.port,
                                                timeout=5, wire_version=1)
            try:
                out = conn.invoke(frame(5.0))
                assert float(out.tensors[0][0]) == 10.0
            finally:
                conn.close()
        finally:
            srv.stop()

    def test_v2_peers_upgrade(self):
        srv = tcp_query.TcpQueryServer(EchoCore(), port=0)
        srv.start()
        try:
            conn = tcp_query.TcpQueryConnection("127.0.0.1", srv.port,
                                                timeout=5)
            try:
                conn.invoke(frame(1.0))
                assert not conn._peer_v1
                assert set(conn._sock_ver.values()) == {2}
            finally:
                conn.close()
        finally:
            srv.stop()

    def test_server_honors_peer_advertised_max_v1(self):
        """A conforming peer that probes 'V' but advertises max version 1
        must NOT be upgraded: the server answers with the AGREED version
        (min of both maxes) and keeps that connection on v1 framing."""
        srv = tcp_query.TcpQueryServer(EchoCore(), port=0)
        srv.start()
        try:
            s = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
            try:
                s.settimeout(5)
                tcp_query._send_msg(s, ord("V"), [b"1"], version=1)
                rtype, body, _ = tcp_query._recv_msg(s, version=1)
                assert rtype == ord("V")
                assert bytes(body) == b"1"  # agreed = min(1, server max)
                # the connection stayed v1-framed: a v1 exchange works
                buf = wire.encode_frame(frame(4.0), version=1)
                tcp_query._send_msg(s, ord("Q"), [buf], version=1)
                rtype, body, _ = tcp_query._recv_msg(s, version=1)
                assert rtype == ord("Q")
                out = wire.decode_frame(body)
                assert float(out.tensors[0][0]) == 8.0
            finally:
                s.close()
        finally:
            srv.stop()

    def test_serversrc_clamps_wire_version_prop(self):
        """An out-of-range wire-version on the serversrc is clamped to a
        version the codecs speak BEFORE it reaches the reply encoders
        (the gRPC path hands core.wire_version straight to
        encode_frame, which refuses unknown versions per request)."""
        pipe = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=973 port=0 "
            "connect-type=tcp wire-version=7 ! "
            "tensor_query_serversink id=973")
        pipe.start()
        try:
            assert pipe["ssrc"]._core.wire_version == 2
        finally:
            pipe.stop()

    def test_pipeline_v2_client_against_v1_framed_peer(self):
        """Acceptance: a v2 client pipeline round-trips against a server
        pinned to wire-version=1 (legacy framing, no checksums)."""
        server = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=961 port=0 "
            "connect-type=tcp wire-version=1 ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=961")
        server.start()
        port = server["ssrc"].props["port"]
        client = parse_pipeline(
            "appsrc name=src ! tensor_query_client name=q connect-type=tcp "
            f"host=localhost port={port} timeout=10 ! tensor_sink name=out")
        client.start()
        try:
            for i in range(4):
                client["src"].push(np.float32([i]))
            client["src"].end_of_stream()
            client.wait(timeout=30)
            vals = [float(f.tensors[0][0]) for f in client["out"].frames]
            assert vals == [0.0, 2.0, 4.0, 6.0]
            # the client element's pool actually negotiated down to v1
            assert all(c._peer_v1 for c in client["q"]._conns)
            assert client.health()["q"]["delivered"] == 4
        finally:
            client.stop()
            server.stop()


# ---------------------------------------------------------------------------
# server survives hostile bytes (raw socket)
# ---------------------------------------------------------------------------
class TestServerHostileInput:
    def _server(self, sid):
        pipe = parse_pipeline(
            f"tensor_query_serversrc name=ssrc id={sid} port=0 "
            "connect-type=tcp ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            f"tensor_query_serversink id={sid}")
        pipe.start()
        return pipe, pipe["ssrc"].props["port"]

    def _recv_reply(self, s):
        head = b""
        while len(head) < 17:
            chunk = s.recv(17 - len(head))
            assert chunk, "server hung up before reply"
            head += chunk
        mtype, blen, _ = struct.unpack("<BQd", head)
        body = b""
        while len(body) < blen:
            chunk = s.recv(blen - len(body))
            assert chunk, "server hung up mid-reply"
            body += chunk
        return mtype, body

    def test_corrupt_query_gets_C_and_connection_survives(self):
        pipe, port = self._server(962)
        try:
            s = socket.create_connection(("127.0.0.1", port), timeout=10)
            bad = bytearray(wire.encode_frame(frame(3.0)))
            bad[-1] ^= 1
            s.sendall(tcp_query.encode_msg(ord("Q"), bytes(bad), 10.0))
            mtype, body = self._recv_reply(s)
            assert mtype == ord("C") and b"checksum" in body
            # SAME connection keeps working
            good = wire.encode_frame(frame(3.0), version=1)
            s.sendall(tcp_query.encode_msg(ord("Q"), good, 10.0))
            mtype, body = self._recv_reply(s)
            assert mtype == ord("Q")
            out = wire.decode_frame(body)
            assert float(out.tensors[0][0]) == 6.0
            s.close()
            h = pipe.health()["ssrc"]
            assert h["corrupt_requests"] == 1
        finally:
            pipe.stop()

    def test_garbage_and_oversize_do_not_kill_server(self):
        pipe, port = self._server(963)
        try:
            # oversize declared body length: typed refusal, conn dropped
            s = socket.create_connection(("127.0.0.1", port), timeout=10)
            s.sendall(struct.pack("<BQd", ord("Q"), wire.MAX_BODY + 1, 0.0))
            mtype, body = self._recv_reply(s)
            assert mtype in (ord("C"), ord("E"))
            s.close()
            # fresh connection still served after the hostile one
            conn = tcp_query.TcpQueryConnection("127.0.0.1", port, timeout=10)
            try:
                out = conn.invoke(frame(4.0))
                assert float(out.tensors[0][0]) == 8.0
            finally:
                conn.close()
        finally:
            pipe.stop()


# ---------------------------------------------------------------------------
# corruption chaos e2e (acceptance)
# ---------------------------------------------------------------------------
@pytest.mark.chaos
class TestCorruptionChaosE2E:
    def _run(self, site, sid, n=24):
        server = parse_pipeline(
            f"tensor_query_serversrc name=ssrc id={sid} port=0 "
            "connect-type=tcp ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            f"tensor_query_serversink id={sid}")
        server.start()
        port = server["ssrc"].props["port"]
        client = parse_pipeline(
            "appsrc name=src ! tensor_query_client name=q connect-type=tcp "
            f"host=localhost port={port} retries=2 retry-backoff=0.01 "
            "corrupt-retries=3 breaker-threshold=0 degrade=skip timeout=10 "
            "max-in-flight=2 ! tensor_sink name=out")
        client.start()
        # arm AFTER start: the caps handshake must not draw faults
        FAULTS.arm(site, corrupt="bitflip", every=3, seed=11)
        try:
            for i in range(n):
                client["src"].push(np.float32([i]))
            client["src"].end_of_stream()
            client.wait(timeout=60)
            fired = FAULTS.stats(site)["fired"]
            h = client.health()["q"]
            vals = sorted(float(f.tensors[0][0])
                          for f in client["out"].frames)
            return n, fired, h, vals, server.health()["ssrc"]
        finally:
            FAULTS.reset()
            client.stop()
            server.stop()

    def test_send_corruption_exact_accounting_server_alive(self):
        """corrupt= on tcp_query.send: the server answers every corrupt
        request with 'C' and never dies; the client resends and delivers
        everything, with exact delivered/retried/corruption accounting."""
        n, fired, h, vals, server_h = self._run("tcp_query.send", 971)
        assert fired > 0
        # every fired corruption was DETECTED (nothing served garbage)
        assert h["corruption_detected"] == fired
        # exact delivery accounting: answered + skipped == pushed
        assert h["delivered"] + h["degraded_frames"] == n
        assert len(vals) + h["degraded_frames"] == n
        assert set(vals) <= {i * 2.0 for i in range(n)}
        assert len(set(vals)) == len(vals)
        # every detection was either retried or (rarely) degraded
        assert h["retried"] >= h["corruption_detected"] - h["degraded_frames"]
        assert h["degraded_frames"] <= 2
        # the server counted and survived every corrupt request
        assert server_h["corrupt_requests"] == fired

    def test_recv_corruption_exact_accounting(self):
        """corrupt= on tcp_query.recv: corrupted REPLIES are detected at
        decode, counted, and re-asked (resend-safe per the integrity
        contract) — the stream still delivers everything."""
        n, fired, h, vals, server_h = self._run("tcp_query.recv", 972)
        assert fired > 0
        assert h["corruption_detected"] == fired
        assert h["delivered"] + h["degraded_frames"] == n
        assert len(vals) + h["degraded_frames"] == n
        assert len(set(vals)) == len(vals)
        assert h["degraded_frames"] <= 2
        # reply corruption happens client-side; the server saw clean requests
        assert server_h["corrupt_requests"] == 0

    def test_sustained_corruption_trips_breaker_single_does_not(self):
        """Acceptance: one corrupt reply never trips the breaker;
        corruption on EVERY exchange does."""
        server = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=973 port=0 "
            "connect-type=tcp ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=973")
        server.start()
        port = server["ssrc"].props["port"]
        client = parse_pipeline(
            "appsrc name=src ! tensor_query_client name=q connect-type=tcp "
            f"host=localhost port={port} retries=0 retry-backoff=0 "
            "corrupt-retries=2 breaker-threshold=3 breaker-reset=60 "
            "degrade=skip timeout=10 max-in-flight=1 ! tensor_sink name=out")
        client.start()
        try:
            # phase 1: exactly one corrupt exchange
            FAULTS.arm("tcp_query.send", corrupt="bitflip", every=1,
                       times=1, seed=3)
            client["src"].push(np.float32([1]))
            deadline = time.time() + 20
            while (client.health()["q"]["delivered"] < 1
                   and time.time() < deadline):
                time.sleep(0.05)
            h = client.health()["q"]
            assert h["corruption_detected"] == 1
            snap = h["breakers"][f"localhost:{port}"]
            assert snap["state"] == "closed" and snap["trips"] == 0
            # phase 2: corruption on every exchange trips it
            FAULTS.arm("tcp_query.send", corrupt="bitflip", every=1, seed=3)
            for i in range(4):
                client["src"].push(np.float32([10 + i]))
            client["src"].end_of_stream()
            client.wait(timeout=60)
            h = client.health()["q"]
            assert h["breakers"][f"localhost:{port}"]["trips"] >= 1
            assert h["corruption_detected"] > 1
            # nothing lost silently: delivered + degraded == pushed
            assert h["delivered"] + h["degraded_frames"] == 5
        finally:
            FAULTS.reset()
            client.stop()
            server.stop()


# ---------------------------------------------------------------------------
# grpc transport: DATA_LOSS parity
# ---------------------------------------------------------------------------
class TestGrpcCorruptRequest:
    def test_corrupt_request_data_loss_and_server_survives(self):
        import grpc

        from nnstreamer_tpu.distributed.service import QueryConnection

        server = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=974 port=0 ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=974")
        server.start()
        port = server["ssrc"].props["port"]
        conn = QueryConnection("localhost", port, timeout=10)
        try:
            bad = bytearray(wire.encode_frame(frame(3.0)))
            bad[-2] ^= 1
            with pytest.raises(WireCorruptionError):
                try:
                    conn._invoke(bytes(bad), timeout=10)
                except grpc.RpcError as e:
                    assert e.code() == grpc.StatusCode.DATA_LOSS
                    conn._map_busy(e)
                    raise
            # the server survived and still answers clean requests
            out = conn.invoke(frame(3.0))
            assert float(out.tensors[0][0]) == 6.0
            assert server.health()["ssrc"]["corrupt_requests"] == 1
        finally:
            conn.close()
            server.stop()


# ---------------------------------------------------------------------------
# pub/sub transports: verify-on-decode drops corrupt frames, stream lives
# ---------------------------------------------------------------------------
class TestPubSubCorruptDrop:
    def test_tcp_edge_corrupt_frames_dropped_and_counted(self):
        tx = parse_pipeline(
            "appsrc name=src ! edgesink name=es connect-type=tcp port=0 "
            "topic=integ")
        tx.start()
        port = tx["es"].props["port"]
        rx = parse_pipeline(
            f"edgesrc name=e connect-type=tcp dest-host=127.0.0.1 "
            f"dest-port={port} topic=integ ! tensor_sink name=out")
        rx.start()
        try:
            deadline = time.time() + 10
            while (tx["es"]._tcp.subscriber_count("integ") < 1
                   and time.time() < deadline):
                time.sleep(0.02)
            FAULTS.arm("tcp_edge.publish", corrupt="bitflip", every=2, seed=2)
            for i in range(6):
                tx["src"].push(np.float32([i]))
            deadline = time.time() + 15
            while (len(rx["out"].frames) < 3 and time.time() < deadline):
                time.sleep(0.05)
            fired = FAULTS.stats("tcp_edge.publish")["fired"]
            assert fired == 3  # every=2 over 6 publishes
            vals = [float(f.tensors[0][0]) for f in rx["out"].frames]
            assert vals == [1.0, 3.0, 5.0]  # corrupted 0/2/4 dropped
            assert rx.health()["e"]["corrupt_dropped"] == 3
        finally:
            FAULTS.reset()
            tx["src"].end_of_stream()
            tx.wait(timeout=10)
            rx.stop()
            tx.stop()

    def test_mqtt_corrupt_frames_dropped_and_counted(self):
        from nnstreamer_tpu.distributed.mqtt import MiniBroker

        broker = MiniBroker()
        try:
            rx = parse_pipeline(
                f"mqttsrc name=m host=127.0.0.1 port={broker.port} "
                "sub-topic=integ num-buffers=2 sub-timeout=20000 ! "
                "tensor_sink name=out")
            rx.start()
            assert broker.wait_subscriber("integ", 10.0)
            tx = parse_pipeline(
                f"appsrc name=src ! mqttsink host=127.0.0.1 "
                f"port={broker.port} pub-topic=integ")
            tx.start()
            FAULTS.arm("mqtt.publish", corrupt="bitflip", every=2, seed=4)
            for i in range(4):
                tx["src"].push(np.float32([i]))
            tx["src"].end_of_stream()
            tx.wait(timeout=15)
            rx.wait(timeout=30)
            vals = [float(f.tensors[0][0]) for f in rx["out"].frames]
            assert vals == [1.0, 3.0]  # messages 0/2 corrupted, dropped
            assert rx.health()["m"]["corrupt_dropped"] == 2
            tx.stop()
            rx.stop()
        finally:
            FAULTS.reset()
            broker.close()


# ---------------------------------------------------------------------------
# datarepo: crash-atomic writes + truncation-tolerant reads (satellite)
# ---------------------------------------------------------------------------
class TestDatarepoCrashAtomic:
    def _write_repo(self, data, meta, n=4):
        pipe = parse_pipeline(
            f"appsrc name=src ! datareposink location={data} json={meta}")
        pipe.start()
        for i in range(n):
            pipe["src"].push(np.full((2,), i, np.float32))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=20)
        pipe.stop()

    def test_killed_writer_leaves_previous_repo_intact(self, tmp_path):
        from nnstreamer_tpu.elements.datarepo import DataRepoSink

        data, meta = tmp_path / "d.bin", tmp_path / "d.json"
        self._write_repo(data, meta, n=2)
        before = data.read_bytes()
        # second run killed mid-write: render without stop()
        sink = DataRepoSink("s")
        sink.set_property("location", str(data))
        sink.set_property("json", str(meta))
        sink.start()
        sink.render(TensorFrame([np.float32([9.0, 9.0])]))
        # no stop(): simulated kill.  The published repo is untouched
        assert data.read_bytes() == before
        assert json.loads(meta.read_text())["total_samples"] == 2
        # the partial write sits in a dot-tmp sibling only
        assert any(p.name.startswith(".tmp-") for p in tmp_path.iterdir())

    def test_clean_stop_publishes_atomically(self, tmp_path):
        data, meta = tmp_path / "d.bin", tmp_path / "d.json"
        self._write_repo(data, meta, n=3)
        assert data.stat().st_size == 3 * 8
        m = json.loads(meta.read_text())
        assert m["total_samples"] == 3 and m["sample_size"] == 8
        assert not any(p.name.startswith(".tmp-") for p in tmp_path.iterdir())

    def test_truncated_trailing_sample_reported_not_crashed(self, tmp_path):
        data, meta = tmp_path / "d.bin", tmp_path / "d.json"
        self._write_repo(data, meta, n=4)
        # a killed writer left 2 complete samples + half a third
        data.write_bytes(data.read_bytes()[: 2 * 8 + 3])
        pipe = parse_pipeline(
            f"datareposrc name=r location={data} json={meta} ! "
            "tensor_sink name=out")
        pipe.start()
        pipe.wait(timeout=20)
        vals = [float(f.tensors[0][0]) for f in pipe["out"].frames]
        assert vals == [0.0, 1.0]  # the complete prefix, in order
        assert pipe.health()["r"]["truncated_samples"] == 2
        pipe.stop()

    def test_zero_complete_samples_still_fatal(self, tmp_path):
        from nnstreamer_tpu.elements.datarepo import DataRepoSrc
        from nnstreamer_tpu.pipeline.element import ElementError

        data, meta = tmp_path / "d.bin", tmp_path / "d.json"
        self._write_repo(data, meta, n=2)
        data.write_bytes(b"\x00" * 3)
        src = DataRepoSrc("r")
        src.set_property("location", str(data))
        src.set_property("json", str(meta))
        with pytest.raises(ElementError, match="no complete sample"):
            src.start()

    def test_image_mode_atomic_no_tmp_left(self, tmp_path):
        pytest.importorskip("PIL")
        pipe = parse_pipeline(
            f"appsrc name=src ! datareposink "
            f"location={tmp_path}/s_%03d.png json={tmp_path}/s.json")
        pipe.start()
        rng = np.random.default_rng(0)
        for _ in range(2):
            pipe["src"].push(rng.integers(0, 255, (8, 8, 3)).astype(np.uint8))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=20)
        pipe.stop()
        names = sorted(p.name for p in tmp_path.iterdir())
        assert names == ["s.json", "s_000.png", "s_001.png"]
        assert json.loads((tmp_path / "s.json").read_text())[
            "total_samples"] == 2


# ---------------------------------------------------------------------------
# fuzz smoke (tier-1 gate) + integrity-tax bench row
# ---------------------------------------------------------------------------
@pytest.mark.fuzz
def test_fuzz_wire_fixed_seed_smoke():
    """CI contract: the deterministic fuzzer runs >= 10k seeded
    mutations inside tier-1 with zero uncaught exceptions, zero hangs,
    zero over-MAX_BODY allocations (exit 0)."""
    sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
    try:
        import fuzz_wire
    finally:
        sys.path.pop(0)
    assert fuzz_wire.main(["--seed", "7", "--iterations", "10000", "-q"]) == 0


def test_wire_checksum_overhead_is_measured():
    """The integrity tax is measured, not guessed: tools/bench_wire.py
    produces one row per payload size with every documented key
    (Documentation/wire-protocol.md "Cost"), and both envelope versions
    completed their round trips."""
    sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
    try:
        import bench_wire
    finally:
        sys.path.pop(0)
    (row,) = bench_wire.run([65536], 200)
    assert set(row) == {
        "payload_bytes", "iters", "v1_rps", "v2_rps", "v2_noverify_rps",
        "integrity_tax_pct", "verify_crc_mb_s"}
    assert row["iters"] == 200 and row["payload_bytes"] > 65536
    assert row["v1_rps"] > 0 and row["v2_rps"] > 0


def test_fuzz_marker_registered():
    text = (Path(__file__).parent.parent / "pyproject.toml").read_text()
    assert '"fuzz:' in text  # registered marker: tier-1 is warning-clean
