"""Versioned wire format for live serving sessions.

A :class:`~repro.serve.engine.Session` is already transport-shaped — the
request, its decode position, the next input token, and a host-numpy cache
slice — but until now it only moved between engines as an in-process
object.  This module gives it a byte encoding so it can cross a process or
WAN boundary:

``RSES | version | codec | crc32(payload) | compressed msgpack payload``

* the 4-byte magic and one-byte **format version** make foreign or
  future-format payloads fail loudly (``WireFormatError``), never decode
  into garbage;
* the one-byte **codec id** records how the payload was compressed — the
  checkpoint codec path (zstd when the optional ``zstandard`` package is
  present, stdlib zlib otherwise), so a zlib-only build reads any payload
  it can and reports the one it can't;
* the **crc32** of the compressed payload catches truncation and bit rot
  before anything is deserialized;
* the payload itself is msgpack (never pickle — a wire format that
  executes its sender's bytecode is not a wire format), with every numpy
  leaf encoded as ``{dtype, shape, data}`` exactly like checkpoint shards.

``t_first``/``t_admit`` are wall-clock ``perf_counter`` stamps: meaningful
on the host that wrote them (loopback transport), opaque across hosts —
receivers must not compare them against their own clock.
"""

from __future__ import annotations

import struct
import zlib

import msgpack
import numpy as np

from ..checkpoint.store import compress, decompress, default_codec
from ..serve.engine import Request, Session

WIRE_MAGIC = b"RSES"
# v1: the original layout.  v2, v3 and v4 each added one OPTIONAL payload
# key: "trace" (the request's trace context — see repro.obs.trace),
# "prefilled" (the session left its source mid-prefill with that many
# prompt tokens consumed — see Session.prefilled) and "delivery" (the
# monotonic ``(origin, rid, epoch)`` id adoption dedups on, so a retried
# ship never double-adopts — see Session.delivery).  v5 and v6 changed
# the layout of the attention KV leaves: v1-v4 carried ``(..., B, S, Hkv,
# hd)``, v5 ``(..., B, Hkv, S, hd)`` and v6 the sequence-minor cache
# layout ``(..., B, Hkv, hd, S)`` (``models.layers.kv_spec``).  An older
# payload would be inserted as transposed KV (silently, when its length
# fits the axis it lands on), so this build reads v6 only and refuses
# older payloads loudly.  Writers always emit WIRE_VERSION; readers accept
# exactly WIRE_COMPAT.
WIRE_VERSION = 6
WIRE_COMPAT = frozenset({6})
_CODEC_IDS = {"zlib": 0, "zstd": 1}
_CODEC_NAMES = {v: k for k, v in _CODEC_IDS.items()}
# magic(4) + version(1) + codec(1) + crc32(4)
_HEADER = struct.Struct(">4sBBI")


class WireFormatError(ValueError):
    """The payload is not a decodable session: wrong magic, unknown
    version or codec, checksum mismatch, or corrupt body."""


def _pack_array(a: np.ndarray) -> dict:
    a = np.asarray(a)
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "data": a.tobytes()}


def _unpack_array(d: dict) -> np.ndarray:
    # .copy(): frombuffer views are read-only and pin the payload bytes
    return np.frombuffer(d["data"], dtype=d["dtype"]).reshape(
        d["shape"]).copy()


def encode_session(sess: Session, codec: str | None = None) -> bytes:
    """Serialize a session for transport.  ``codec`` defaults to the best
    one this build can write (the checkpoint codec path)."""
    codec = codec if codec is not None else default_codec()
    if codec not in _CODEC_IDS:
        raise WireFormatError(f"unknown wire codec {codec!r}")
    req = sess.req
    payload = {
        "req": {
            "rid": int(req.rid),
            "prompt": _pack_array(req.prompt),
            "max_new": int(req.max_new),
            "tenant": req.tenant,
            "extras": {k: _pack_array(v) for k, v in req.extras.items()},
            "out_tokens": [int(t) for t in req.out_tokens],
            "done": bool(req.done),
            "t_first": req.t_first,
            "t_admit": req.t_admit,
        },
        "pos": int(sess.pos),
        "cur_token": int(sess.cur_token),
        "cache": {k: _pack_array(v) for k, v in sess.cache.items()},
    }
    if sess.trace is not None:
        # v2's optional trace context: the request's causal identity rides
        # the wire so the importing engine continues the same timeline
        payload["trace"] = sess.trace
    if sess.prefilled is not None:
        # v3's optional partial-prefill marker: the importing engine must
        # resume chunked prefill at this offset, not start decoding
        payload["prefilled"] = int(sess.prefilled)
    if sess.delivery is not None:
        # v4's optional delivery id: (origin replica/fleet, rid, epoch) —
        # a retried or duplicated ship re-delivers the SAME id, so the
        # adopting gateway can recognize and drop the second copy
        o, rid, epoch = sess.delivery
        payload["delivery"] = [int(o), int(rid), int(epoch)]
    body = compress(msgpack.packb(payload, use_bin_type=True), codec)
    header = _HEADER.pack(WIRE_MAGIC, WIRE_VERSION, _CODEC_IDS[codec],
                          zlib.crc32(body) & 0xFFFFFFFF)
    return header + body


def wire_header(data: bytes) -> dict:
    """Parse and validate just the header: ``{version, codec, nbytes}``.
    Cheap enough for routing/stats layers that never decode the body."""
    if len(data) < _HEADER.size:
        raise WireFormatError(
            f"payload too short for a session wire header "
            f"({len(data)} < {_HEADER.size} bytes)")
    magic, version, codec_id, crc = _HEADER.unpack_from(data)
    if magic != WIRE_MAGIC:
        raise WireFormatError(
            f"bad magic {magic!r}: not a session wire payload")
    if version not in WIRE_COMPAT:
        # explicit compat set: the CRC covers only the body, so a corrupted
        # version byte (e.g. 5 -> 4) must fail HERE, not be decoded under
        # the wrong layout
        raise WireFormatError(
            f"unsupported session wire version {version} "
            f"(this build reads {sorted(WIRE_COMPAT)})")
    codec = _CODEC_NAMES.get(codec_id)
    if codec is None:
        raise WireFormatError(f"unknown wire codec id {codec_id}")
    return {"version": version, "codec": codec, "crc": crc,
            "nbytes": len(data)}


def verify_crc(data: bytes) -> dict:
    """Header check plus body-CRC check, *without* decoding the body.

    This is the receiver-integrity half of :func:`decode_session`, split
    out so the reliable-delivery layer (:mod:`repro.chaos.reliable`) can
    decide delivered-intact vs retry without paying decompression for
    payloads that will just be resent.  Raises :class:`WireFormatError`
    on any mismatch; returns the parsed header on success."""
    h = wire_header(data)
    if (zlib.crc32(data[_HEADER.size:]) & 0xFFFFFFFF) != h["crc"]:
        raise WireFormatError("session payload checksum mismatch "
                              "(truncated or corrupt)")
    return h


def decode_session(data: bytes) -> Session:
    """Reconstruct a session from :func:`encode_session` bytes.

    Every failure mode — foreign bytes, a future format version, a codec
    this build can't read, truncation, corruption — raises
    :class:`WireFormatError` with the specific cause; nothing is ever
    deserialized from a payload whose checksum doesn't match.  The decoded
    session carries a *new* :class:`Request` object (the sender's handle
    stays frozen at export — cross-boundary identity is the ``rid``)."""
    h = verify_crc(data)
    body = data[_HEADER.size:]
    try:
        raw = decompress(body, h["codec"])
        payload = msgpack.unpackb(raw, raw=False, strict_map_key=False)
        r = payload["req"]
        req = Request(rid=r["rid"], prompt=_unpack_array(r["prompt"]),
                      max_new=r["max_new"], tenant=r["tenant"],
                      extras={k: _unpack_array(v)
                              for k, v in r["extras"].items()},
                      out_tokens=list(r["out_tokens"]), done=r["done"],
                      t_first=r["t_first"], t_admit=r["t_admit"])
        # optional keys: the writer omits each one that is None
        delivery = payload.get("delivery")
        return Session(req=req, pos=payload["pos"],
                       cur_token=payload["cur_token"],
                       cache={k: _unpack_array(v)
                              for k, v in payload["cache"].items()},
                       trace=payload.get("trace"),
                       prefilled=payload.get("prefilled"),
                       delivery=(tuple(delivery) if delivery is not None
                                 else None))
    except WireFormatError:
        raise
    except RuntimeError as e:
        # codec named in the header but not importable on this build
        # (zstd payload, zlib-only receiver): still a WireFormatError —
        # the caller's reject-and-requeue path must catch it
        raise WireFormatError(str(e)) from e
    except Exception as e:      # zlib/msgpack/shape errors: corrupt body
        raise WireFormatError(
            f"session payload failed to decode ({e})") from e
