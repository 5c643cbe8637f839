"""Wire serialization for tensor frames.

The reference ships ``other/tensors`` over the wire via protobuf/flatbuf
IDLs (``ext/nnstreamer/include/nnstreamer.proto``/``.fbs``) or
nnstreamer-edge's custom TCP framing.  This is the TPU build's framing: a
compact self-describing binary layout reusing the flexible-tensor header
from the core type system (one schema for in-process flexible streams AND
the wire — the reference keeps two).

Frame layout (little-endian):

  v1: u32 magic 'NNSQ' | u16 version=1 | u64 seq | f64 pts (NaN = none) |
      u32 meta_len | meta JSON | u16 ntensors |
      per tensor: flex header | u64 payload_len | raw bytes

  v2: identical, except version=2 and the fixed header grows a trailing
      u32 CRC-32 (zlib) computed over the ENTIRE encoded frame with the
      crc field zeroed — header fields, meta, flex headers, and tensor
      payloads are all covered, so any single flipped bit on the wire is
      detected at decode instead of served as a silently-garbage tensor.

Batch envelope (wire micro-batching):

  v1: u32 magic 'NNSB' | u16 count | per frame: u64 len | NNSQ bytes
  v2: u32 magic 'NNSC' | u16 count | u32 crc | per frame: u64 len | bytes
      The batch crc covers the SKELETON (header with crc zeroed + every
      u64 length prefix); frame contents are already covered by their own
      per-frame v2 checksums, so the envelope never pays a second pass
      over the payload bytes.

Integrity contract (Documentation/wire-protocol.md):

* ``decode_frame``/``decode_frames`` validate EVERY declared size
  (meta_len, tensor count, rank, dtype, payload/entry lengths) against
  hard limits and the actual buffer BEFORE any allocation or
  ``frombuffer`` — hostile input can neither crash the decoder with a raw
  ``struct``/numpy error nor make it allocate beyond :data:`MAX_BODY`.
* Every malformed input raises a typed :class:`WireError` subclass:
  :class:`WireTruncationError` (buffer ends before declared data) or
  :class:`WireCorruptionError` (checksum mismatch / internally
  inconsistent or implausible fields).  Both are marked transient
  (``nns_transient``) — corruption is a property of one transmission,
  not of the stream.
* v2 decoders accept v1 frames (a v2 node interoperates with v1 peers on
  receive); a v1 decoder rejects v2, so senders negotiate (tcp_query 'V'
  handshake) or pin ``NNS_WIRE_V=1`` for fleet rollback.
"""

from __future__ import annotations

import json
import math
import os
import struct
import zlib
from typing import Any, Dict

import numpy as np

from ..core.buffer import TensorFrame
from ..core.liveness import DEADLINE_META
from ..core.telemetry import TL_PREFIX as _TL_PREFIX
from ..core.telemetry import TRACE_ID_META
from ..core.tracer import META_SRC_TS as _SRC_TS_META
from ..core.types import (
    TENSOR_COUNT_LIMIT,
    FlexHeaderTruncated,
    TensorSpec,
    pack_flex_header,
    unpack_flex_header,
)

_MAGIC = 0x4E4E5351  # 'NNSQ'
V1 = 1
V2 = 2

#: hard cap on any peer-declared body/payload length before allocation —
#: shared with every transport (≙ gRPC max_receive_message_length)
MAX_BODY = 512 * 1024 * 1024
#: sane bound on the JSON meta blob inside one frame
MAX_META = 16 * 1024 * 1024

_HEAD1 = struct.Struct("<IHQdI")
_HEAD2 = struct.Struct("<IHQdII")  # v2: + u32 crc32 (over frame, crc zeroed)
_MAGVER = struct.Struct("<IH")
_CRC_OFF = _HEAD1.size  # the crc field rides at the end of the v2 header
_ZERO4 = b"\x00\x00\x00\x00"
_NT = struct.Struct("<H")
_PLEN = struct.Struct("<Q")


def default_version() -> int:
    """Envelope version encoders use when none is given.  ``NNS_WIRE_V=1``
    is the fleet-rollback knob: it pins every encoder in this process
    back to checksum-free v1 frames (decoders accept both regardless)."""
    return V1 if os.environ.get("NNS_WIRE_V", "") == "1" else V2


class WireError(ValueError):
    """Base class for every malformed-wire-data condition."""


class WireCorruptionError(WireError):
    """Bytes parsed but can't be trusted: checksum mismatch, bad magic,
    or internally inconsistent / implausible declared fields."""

    #: resilience classification (core/resilience.py): corruption is a
    #: property of ONE transmission — retrying the exchange may succeed
    nns_transient = True


class WireTruncationError(WireError):
    """The buffer ends before the data its headers declare."""

    nns_transient = True


def get_codec(name: str):
    """(encode, decode) for a wire IDL name.

    ``flex``/``nnsq`` = this module's compact framing (default);
    ``protobuf`` = interop IDL #1 (``protobuf_codec.py``,
    ≙ reference nnstreamer.proto + nnstreamer_grpc_protobuf.cc);
    ``flatbuf`` = interop IDL #2 (``flatbuf_codec.py``, the reference's
    actual nnstreamer.fbs binary schema).

    Every decode callable accepts ``verify=`` (the flex codec checks its
    v2 CRC; the interop IDLs have no checksum field and ignore it).
    """
    if name in ("", "flex", "nnsq"):
        return encode_frame, decode_frame
    if name == "protobuf":
        from . import protobuf_codec

        return protobuf_codec.encode_frame, protobuf_codec.decode_frame
    if name == "flatbuf":
        from . import flatbuf_codec

        return flatbuf_codec.encode_frame, flatbuf_codec.decode_frame
    raise WireError(f"unknown wire idl {name!r} (flex|protobuf|flatbuf)")


def _clean_meta(meta: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for k, v in meta.items():
        if k == DEADLINE_META or k == _SRC_TS_META or k.startswith(
                _TL_PREFIX):
            # host-local instants never cross the wire: the deadline
            # stamp (core/liveness.py — the remaining BUDGET travels in
            # the transport header instead), the tracer's interlatency
            # origin stamp, and every trace-local telemetry key
            # (core/telemetry.py TL_PREFIX — client enqueue / server rx
            # stamps).  Only DURATIONS travel (SRV_SPAN_META), and the
            # receiver re-stamps on its own clock.
            continue
        try:
            json.dumps(v)
            out[k] = v
        except (TypeError, ValueError):
            continue  # non-serializable entries stay process-local
    return out


def _check_version(version) -> int:
    version = int(version)
    if version not in (V1, V2):
        raise WireError(f"cannot encode wire version {version} (have 1|2)")
    return version


def encode_frame_parts(frame: TensorFrame, version: int = None) -> list:
    """Vectored encoding: the frame as a list of buffer objects with NO
    payload copies — tensor data rides as memoryviews of the arrays.
    Callers either gather-send the parts directly (``socket.sendmsg``,
    zero user-space copies) or join them (``encode_frame``).

    v2 (default): the header carries a CRC-32 over the whole frame (crc
    field zeroed) — computed in one streaming pass over the parts, still
    without copying any payload."""
    version = default_version() if version is None else _check_version(version)
    meta = json.dumps(_clean_meta(frame.meta)).encode()
    pts = frame.pts if frame.pts is not None else math.nan
    head = (
        _HEAD2.pack(_MAGIC, V2, frame.seq, pts, len(meta), 0)
        if version == V2
        else _HEAD1.pack(_MAGIC, V1, frame.seq, pts, len(meta))
    )
    parts = [head, meta, _NT.pack(len(frame.tensors))]
    for t in frame.tensors:
        arr = np.ascontiguousarray(np.asarray(t))
        spec = TensorSpec(tuple(arr.shape), arr.dtype)
        parts.append(pack_flex_header(spec))
        parts.append(_PLEN.pack(arr.nbytes))
        parts.append(arr.reshape(-1).view(np.uint8).data)
    if version == V2:
        crc = 0
        for p in parts:
            crc = zlib.crc32(p, crc)
        parts[0] = _HEAD2.pack(_MAGIC, V2, frame.seq, pts, len(meta), crc)
    return parts


def note_frame(sp, frame: TensorFrame) -> None:
    """Name a streaming client's ``nns.query.recv`` span by the frame it
    received: the request's trace id and the chunk, so that the frame's
    server record (``nns.gen.deliver``) and its receipt join on (trace,
    chunk), and its tokens (what a client counts of a chunk: its first
    tensor's elements)."""
    sp.set(request=frame.meta.get(TRACE_ID_META),
           chunk=frame.meta.get("chunk_index"),
           tokens=int(np.size(frame.tensors[0])) if frame.tensors else 0)


def parts_nbytes(parts) -> int:
    return sum(memoryview(p).nbytes for p in parts)


def encode_frame(frame: TensorFrame, version: int = None) -> bytes:
    return b"".join(bytes(p) if not isinstance(p, bytes) else p
                    for p in encode_frame_parts(frame, version=version))


# -- multi-frame envelope (wire micro-batching) -----------------------------
_BMAGIC = 0x4E4E5342   # 'NNSB' (v1, no checksum)
_B2MAGIC = 0x4E4E5343  # 'NNSC' (v2, skeleton crc)
_BHEAD = struct.Struct("<IH")
_B2HEAD = struct.Struct("<IHI")
_BLEN = struct.Struct("<Q")


def encode_frames_parts(frames, version: int = None) -> list:
    """Vectored multi-frame envelope — no payload copies, for
    gather-sends.  v2 adds a skeleton CRC-32 (batch header + every length
    prefix); the frames inside carry their own v2 checksums."""
    version = default_version() if version is None else _check_version(version)
    if version == V1:
        parts = [_BHEAD.pack(_BMAGIC, len(frames))]
        for f in frames:
            fparts = encode_frame_parts(f, version=V1)
            parts.append(_BLEN.pack(parts_nbytes(fparts)))
            parts.extend(fparts)
        return parts
    head0 = _B2HEAD.pack(_B2MAGIC, len(frames), 0)
    parts = [head0]
    crc = zlib.crc32(head0)
    for f in frames:
        fparts = encode_frame_parts(f, version=V2)
        blen = _BLEN.pack(parts_nbytes(fparts))
        crc = zlib.crc32(blen, crc)
        parts.append(blen)
        parts.extend(fparts)
    parts[0] = _B2HEAD.pack(_B2MAGIC, len(frames), crc)
    return parts


def encode_frames(frames, version: int = None) -> bytes:
    """Pack several frames into ONE envelope.  The query path uses this
    to amortize per-RPC transport overhead over a micro-batch — the wire
    analog of the filter's batched XLA invoke."""
    return b"".join(bytes(p) if not isinstance(p, bytes) else p
                    for p in encode_frames_parts(frames, version=version))


def _need(have: int, off: int, n: int, what: str) -> None:
    """Bounds gate run before EVERY read of declared data: truncated and
    hostile-length inputs fail typed here, never as struct/numpy errors
    or oversized allocations."""
    if off + n > have:
        raise WireTruncationError(
            f"truncated: {what} needs {n} byte(s) at offset {off}, "
            f"buffer has {have}"
        )


def decode_frames(buf, verify: bool = True):
    """Inverse of :func:`encode_frames`; returns a list of frames.

    Strict bounded decode: entry lengths are validated against
    :data:`MAX_BODY` and the real buffer before any slice; a v2 envelope
    additionally has its skeleton checksum verified (``verify=True``)."""
    mv = memoryview(buf)
    total = len(mv)
    _need(total, 0, 4, "batch magic")
    (magic,) = struct.unpack_from("<I", mv, 0)
    if magic == _BMAGIC:
        _need(total, 0, _BHEAD.size, "batch header")
        _, count = _BHEAD.unpack_from(mv, 0)
        off = _BHEAD.size
        crc = None
    elif magic == _B2MAGIC:
        _need(total, 0, _B2HEAD.size, "batch header")
        _, count, crc = _B2HEAD.unpack_from(mv, 0)
        off = _B2HEAD.size
    else:
        raise WireCorruptionError(f"bad batch magic 0x{magic:08x}")
    if crc is not None and verify:
        # skeleton pass: header (crc zeroed) + every length prefix, with
        # the same bounds checks the decode pass below applies
        actual = zlib.crc32(_B2HEAD.pack(_B2MAGIC, count, 0))
        woff = off
        for i in range(count):
            _need(total, woff, _BLEN.size, f"batch entry {i} length")
            actual = zlib.crc32(mv[woff : woff + _BLEN.size], actual)
            (blen,) = _BLEN.unpack_from(mv, woff)
            woff += _BLEN.size
            if blen > MAX_BODY:
                raise WireCorruptionError(
                    f"batch frame {i} declares {blen} bytes (cap {MAX_BODY})"
                )
            _need(total, woff, blen, f"batch frame {i}")
            woff += blen
        if actual != crc:
            raise WireCorruptionError(
                f"batch checksum mismatch (crc32 {actual:#010x} != "
                f"declared {crc:#010x})"
            )
    frames = []
    for i in range(count):
        _need(total, off, _BLEN.size, f"batch entry {i} length")
        (blen,) = _BLEN.unpack_from(mv, off)
        off += _BLEN.size
        if blen > MAX_BODY:
            raise WireCorruptionError(
                f"batch frame {i} declares {blen} bytes (cap {MAX_BODY})"
            )
        _need(total, off, blen, f"batch frame {i}")
        # no copy: decode_frame works on any buffer (memoryview slicing)
        frames.append(decode_frame(mv[off : off + blen], verify=verify))
        off += blen
    if off != total:
        raise WireCorruptionError(
            f"{total - off} trailing byte(s) after batch envelope"
        )
    return frames


def is_batch_payload(buf) -> bool:
    return (
        len(buf) >= 4
        and struct.unpack_from("<I", memoryview(buf), 0)[0]
        in (_BMAGIC, _B2MAGIC)
    )


def frame_version(buf) -> int:
    """Peek the envelope version of one encoded frame (negotiation and
    test helper); raises typed WireErrors like :func:`decode_frame`."""
    mv = memoryview(buf)
    _need(len(mv), 0, _MAGVER.size, "frame magic/version")
    magic, version = _MAGVER.unpack_from(mv, 0)
    if magic != _MAGIC:
        raise WireCorruptionError(f"bad frame magic 0x{magic:08x}")
    return version


def decode_frame(buf, verify: bool = True) -> TensorFrame:
    """Decode one frame (v1 or v2 envelope) with zero payload copies.

    ``verify=True`` (default) checks the v2 CRC-32 before anything else —
    one streaming pass over the buffer, the whole integrity tax (see
    ``tools/bench_wire.py``); v1 frames have no checksum to check.
    Every malformed input raises :class:`WireTruncationError` or
    :class:`WireCorruptionError`; nothing is allocated or reshaped until
    the fields describing it have been validated."""
    mv = memoryview(buf)
    have = len(mv)
    version = frame_version(mv)
    if version == V2:
        _need(have, 0, _HEAD2.size, "v2 frame header")
        _, _, seq, pts, meta_len, crc = _HEAD2.unpack_from(mv, 0)
        if verify:
            actual = zlib.crc32(mv[:_CRC_OFF])
            actual = zlib.crc32(_ZERO4, actual)
            actual = zlib.crc32(mv[_HEAD2.size:], actual)
            if actual != crc:
                raise WireCorruptionError(
                    f"frame checksum mismatch (crc32 {actual:#010x} != "
                    f"declared {crc:#010x})"
                )
        off = _HEAD2.size
    elif version == V1:
        _need(have, 0, _HEAD1.size, "frame header")
        _, _, seq, pts, meta_len = _HEAD1.unpack_from(mv, 0)
        off = _HEAD1.size
    else:
        # a bit flipped INSIDE the version field evades the CRC (the
        # field selects which header to verify), so an unknown version
        # is corruption — typed and transient like every other case
        raise WireCorruptionError(f"unsupported wire version {version}")
    if meta_len > MAX_META:
        raise WireCorruptionError(
            f"implausible meta length {meta_len} (cap {MAX_META})"
        )
    _need(have, off, meta_len, "frame meta")
    if meta_len:
        try:
            meta = json.loads(bytes(mv[off : off + meta_len]).decode())
        except (UnicodeDecodeError, ValueError) as e:
            raise WireCorruptionError(f"malformed frame meta: {e}") from None
        if not isinstance(meta, dict):
            raise WireCorruptionError("frame meta is not a JSON object")
    else:
        meta = {}
    off += meta_len
    _need(have, off, _NT.size, "tensor count")
    (ntensors,) = _NT.unpack_from(mv, off)
    off += _NT.size
    if ntensors > TENSOR_COUNT_LIMIT:
        raise WireCorruptionError(
            f"tensor count {ntensors} exceeds limit {TENSOR_COUNT_LIMIT}"
        )
    tensors = []
    for i in range(ntensors):
        try:
            spec, hlen = unpack_flex_header(mv[off:])
        except FlexHeaderTruncated as e:
            raise WireTruncationError(f"tensor {i}: {e}") from None
        except ValueError as e:
            raise WireCorruptionError(f"tensor {i}: {e}") from None
        off += hlen
        _need(have, off, _PLEN.size, f"tensor {i} payload length")
        (plen,) = _PLEN.unpack_from(mv, off)
        off += _PLEN.size
        # header-consistency BEFORE the buffer check: a corrupted giant
        # plen is corruption, not truncation, and must never reach a
        # frombuffer/reshape (spec.nbytes is exact — flex specs are
        # always concrete, so this also pins payload size to shape*dtype)
        if plen != spec.nbytes:
            raise WireCorruptionError(
                f"tensor {i} payload {plen}B contradicts header "
                f"{tuple(spec.shape)} x {spec.dtype} ({spec.nbytes}B)"
            )
        _need(have, off, plen, f"tensor {i} payload")
        payload = mv[off : off + plen]
        off += plen
        # ALIASING CONTRACT: this view shares memory with the receive
        # buffer (zero-copy decode).  It is explicitly marked
        # read-only — over an immutable bytes buffer numpy already
        # refuses writes, but a pooled/reused bytearray receive buffer
        # would otherwise hand out WRITABLE views, and an in-place
        # downstream transform would silently corrupt every other
        # frame decoded from the same buffer.  Elements that need to
        # mutate must copy first (tensor_transform and friends are
        # out-of-place, so the common pipelines never pay the copy).
        arr = np.frombuffer(payload, dtype=spec.dtype)
        arr.flags.writeable = False
        tensors.append(arr.reshape(spec.shape))
    if off != have:
        raise WireCorruptionError(
            f"{have - off} trailing byte(s) after frame"
        )
    frame = TensorFrame(tensors, pts=None if math.isnan(pts) else pts, meta=meta)
    frame.seq = seq
    return frame
