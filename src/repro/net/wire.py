"""Versioned binary wire format for CRDT gossip and anti-entropy.

The normative specification — frame table, field layouts, size bounds,
and the chunk-streaming / multi-source state machines — lives in
`docs/PROTOCOL.md`; this module is its reference implementation, and
`tests/test_docs.py` asserts the two stay in lockstep.

Frame layout (all integers big-endian):

    magic   2B  b"RN"
    version 1B  0x01 for frame types v1 peers parse; 0x02 for the
                discovery frames v2 introduced (both accepted on decode)
    type    1B  message type tag (MSG_*)
    length  4B  payload byte count
    payload length bytes
    crc32   4B  zlib.crc32 over the payload

The payload is a canonical encoding of one message dataclass: sets are
written in sorted order, dict keys sorted, so encoding is a pure function
of the message value and `encode_message(decode_message(b)) == b` for any
frame this module produced. Tensors travel as raw row-major bytes with a
dtype/shape header; int8-quantized payloads (core.compression) travel as
q-bytes + fp32 scale and reconstruct bit-identically on every replica,
preserving CRDT determinism (paper Assumption 10) across the network
boundary.

Pytree payload values support dict/list/tuple containers and
tensor / CompressedLeaf / scalar leaves — the shapes model contributions
actually take. Unknown structure raises WireError at encode time rather
than producing frames a peer cannot parse.

Large blobs never travel as one frame: payloads whose canonical encoding
exceeds the per-frame data budget are announced via BlobManifest (chunk
count, sizes, per-chunk SHA-256) and stream as ChunkReq/ChunkData frames
bounded by the configured max frame size (DEFAULT_MAX_FRAME). Wire v2
adds the sharded-store discovery frames: HaveReq asks a peer which of a
set of eids it holds, HaveMap answers with complete/partial holdings
(per-chunk bitmaps for partials), and the multi-source scheduler in
`net.antientropy` streams disjoint chunk windows of one blob from
several peers at once.
"""
from __future__ import annotations

import hashlib
import struct
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, FrozenSet, Optional, Tuple

import numpy as np

from repro.core.compression import (
    compressed_tree_from_structure, compressed_tree_to_structure,
    CompressedLeaf, CompressedTree, decompress_tree)
from repro.core.delta import Delta
from repro.core.state import AddEntry, CRDTMergeState
from repro.core.version_vector import VersionVector

MAGIC = b"RN"
VERSION = 2                             # current protocol version
ACCEPTED_VERSIONS = frozenset({1, 2})   # decoded without complaint
# Interop is two-directional: frames whose type already existed in v1
# keep the v1 stamp, so an un-upgraded peer (which rejects version != 1)
# still reads everything it can parse; only the v2-introduced frames
# (HaveReq/HaveMap discovery, ResolveSpecMsg, SparseManifest) carry the
# v2 stamp.
# Decoding is Postel-lenient about the version/type pairing — the type
# tag alone selects the decoder.
HEADER = struct.Struct(">2sBBI")        # magic, version, type, payload len
TRAILER = struct.Struct(">I")           # crc32
FRAME_OVERHEAD = HEADER.size + TRAILER.size

# message type tags
MSG_STATE = 0x01
MSG_DELTA = 0x02
MSG_SYNC_REQ = 0x10
MSG_BUCKETS = 0x11
MSG_BUCKET_ITEMS = 0x12
MSG_BLOB_REQ = 0x13
MSG_BLOB_RESP = 0x14
MSG_SYNC_DONE = 0x15
MSG_BLOB_MANIFEST = 0x16
MSG_CHUNK_REQ = 0x17
MSG_CHUNK_DATA = 0x18
MSG_HAVE_REQ = 0x19
MSG_HAVE_MAP = 0x1A
MSG_RESOLVE_SPEC = 0x1B
MSG_SPARSE_MANIFEST = 0x1C

# Streaming transfer sizing. A multi-GB pytree must never become one
# giant frame: blobs whose canonical encoding exceeds the per-frame data
# budget travel as BlobManifest + ChunkReq/ChunkData instead of BlobResp.
# CHUNK_ENVELOPE reserves room for the non-data fields of a ChunkData
# frame (sender, sid, eid, index, length prefixes, frame overhead) so a
# full chunk plus envelope stays <= the configured max frame size.
DEFAULT_MAX_FRAME = 4 * 2 ** 20
CHUNK_ENVELOPE = 256
DIGEST_LEN = 32                         # per-chunk SHA-256

# value (pytree) node tags
_T_DICT = 0x01
_T_LIST = 0x02
_T_TUPLE = 0x03
_T_TENSOR = 0x04
_T_QLEAF = 0x05
_T_CTREE = 0x06
_T_NONE = 0x07
_T_FLOAT = 0x08
_T_INT = 0x09
_T_STR = 0x0A
_T_BOOL = 0x0B


class WireError(ValueError):
    """Malformed frame, bad checksum, or unsupported value."""


# ---------------------------------------------------------------------------
# Messages
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StateMsg:
    """Full-state push: complete (A, R, V) metadata plus store payloads."""
    sender: str
    adds: FrozenSet[AddEntry]
    removes: FrozenSet[str]
    vv: VersionVector
    payloads: Dict[str, Any] = field(default_factory=dict)

    type = MSG_STATE


@dataclass(frozen=True)
class DeltaMsg:
    """Delta-state push (vv-filtered or bucket-selected entries)."""
    sender: str
    adds: FrozenSet[AddEntry]
    removes: FrozenSet[str]
    vv: VersionVector
    payloads: Dict[str, Any] = field(default_factory=dict)
    compressed: bool = False

    type = MSG_DELTA


@dataclass(frozen=True)
class SyncReq:
    """Anti-entropy round 1: initiator's reconciliation root + bucketing."""
    sender: str
    sid: int
    root: bytes
    bits: int
    vv: VersionVector

    type = MSG_SYNC_REQ


@dataclass(frozen=True)
class BucketsMsg:
    """Round 2: responder's sparse bucket digest vector (roots differ)."""
    sender: str
    sid: int
    bits: int
    digests: Dict[int, bytes]

    type = MSG_BUCKETS


@dataclass(frozen=True)
class BucketItemsMsg:
    """Rounds 3/4: entries in differing buckets; `want` asks the peer to
    reply with its entries for those bucket indices (empty = no reply).
    Carries the session's bucket bit-width so the receiver needs no
    session bookkeeping to interpret `want`."""
    sender: str
    sid: int
    bits: int
    adds: FrozenSet[AddEntry]
    removes: FrozenSet[str]
    vv: VersionVector
    want: Tuple[int, ...] = ()

    type = MSG_BUCKET_ITEMS


@dataclass(frozen=True)
class BlobReq:
    """Request store payloads the requester's store lacks."""
    sender: str
    sid: int
    eids: Tuple[str, ...]

    type = MSG_BLOB_REQ


@dataclass(frozen=True)
class BlobResp:
    sender: str
    sid: int
    payloads: Dict[str, Any] = field(default_factory=dict)
    compressed: bool = False

    type = MSG_BLOB_RESP


@dataclass(frozen=True)
class SyncDone:
    """Roots matched (or session closed); carries vv for metadata merge."""
    sender: str
    sid: int
    vv: VersionVector

    type = MSG_SYNC_DONE


@dataclass(frozen=True)
class ManifestEntry:
    """Chunking of one blob: the canonical encoding of the payload split
    at `chunk_size` boundaries, with a SHA-256 digest per chunk so every
    chunk is verifiable on its own and partial transfers resume without
    re-shipping verified data."""
    eid: str
    chunk_size: int
    total_size: int
    digests: Tuple[bytes, ...]

    @property
    def n_chunks(self) -> int:
        return len(self.digests)


@dataclass(frozen=True)
class BlobManifest:
    """Announces blobs too large for a single BlobResp frame."""
    sender: str
    sid: int
    entries: Tuple[ManifestEntry, ...]

    type = MSG_BLOB_MANIFEST


@dataclass(frozen=True)
class ChunkReq:
    """Request specific chunks of one blob. `chunk_size` echoes the
    manifest the requester adopted, so any peer holding the blob can
    serve compatible chunks regardless of its own chunking config."""
    sender: str
    sid: int
    eid: str
    chunk_size: int
    indices: Tuple[int, ...]

    type = MSG_CHUNK_REQ


@dataclass(frozen=True)
class ChunkData:
    """One verified-size slice of a blob's canonical encoding."""
    sender: str
    sid: int
    eid: str
    index: int
    data: bytes

    type = MSG_CHUNK_DATA


@dataclass(frozen=True)
class HaveReq:
    """Ask a peer which of `eids` it holds (sharded-store discovery).

    The answer (HaveMap) feeds the multi-source chunk scheduler: a
    requester fans disjoint chunk windows of one blob across every peer
    known to hold it."""
    sender: str
    sid: int
    eids: Tuple[str, ...]

    type = MSG_HAVE_REQ


@dataclass(frozen=True)
class HaveEntry:
    """One blob's holding claim. `n_chunks == 0` means the peer holds
    the complete blob (bitmap empty); otherwise `bitmap` marks which of
    the `n_chunks` manifest chunks the peer has verified so far (bit i =
    byte i//8, bit i%8, LSB first)."""
    eid: str
    n_chunks: int
    bitmap: bytes = b""


@dataclass(frozen=True)
class HaveMap:
    """Compact advertisement of which requested eids/chunks a node holds."""
    sender: str
    sid: int
    entries: Tuple[HaveEntry, ...] = ()

    type = MSG_HAVE_MAP


@dataclass(frozen=True)
class LeafRef:
    """Per-leaf planner metadata of one contribution: canonical keystr
    path, `tensor_digest`, dtype name, shape. A SparseManifest full of
    these lets the receiver plan per-leaf contribution subsets — and
    complete warm or fold-resumable resolves — before (or without)
    fetching a single payload chunk.

    `scale` announces that the leaf's payload travels as symmetric int8
    (`CompressedLeaf`) with this fp32 dequantization scale; zero-point
    is identically 0 by construction (the codec is symmetric), so the
    scale alone fully determines dequantization. The digest still
    describes the DEQUANTIZED tensor — content identity is defined on
    wire-format values — which is what lets a receiver plan against the
    int8 bytes without densifying them."""
    path: str
    digest: bytes                  # 32B tensor_digest
    dtype: str
    shape: Tuple[int, ...]
    scale: Optional[float] = None  # int8 dequant scale; None = dense


@dataclass(frozen=True)
class SparseManifestEntry:
    """One contribution's leaf-level announcement: the chunking manifest
    of its canonical blob encoding (so chunk transfer can start from the
    same frame) plus one LeafRef per carried leaf, sorted by path. The
    leaf list IS the coverage descriptor; a dense contribution is the
    trivially-full case (every model leaf listed)."""
    manifest: ManifestEntry
    leaves: Tuple[LeafRef, ...]

    @property
    def eid(self) -> str:
        return self.manifest.eid

    @property
    def coverage(self) -> Tuple[str, ...]:
        return tuple(l.path for l in self.leaves)


@dataclass(frozen=True)
class SparseManifest:
    """Announces contributions at leaf granularity (wire v2): per-leaf
    blob refs feed the planner's digest memo (`engine.note_meta`), and
    the embedded chunk manifests register the sender as a chunk source
    — so a receiver fetches only the payloads some cache-missed leaf
    actually needs (O(changed) fetch)."""
    sender: str
    sid: int
    entries: Tuple[SparseManifestEntry, ...]

    type = MSG_SPARSE_MANIFEST


@dataclass(frozen=True)
class ResolveSpecMsg:
    """Gossip *what to resolve*: a `repro.api.MergeSpec` in its
    canonical encoding. Contributions already converge via the OR-Set;
    this frame lets nodes converge on the resolve description too
    (strategy, typed cfg, base ref, reduction, trust threshold) instead
    of relying on out-of-band configuration. The payload is the spec's
    own versioned canonical bytes — the same bytes its digest() (and
    therefore the engine cache key) hashes."""
    sender: str
    sid: int
    spec: Any                  # repro.api.MergeSpec

    type = MSG_RESOLVE_SPEC


Message = Any  # any of the dataclasses above


# ---------------------------------------------------------------------------
# Primitive encoders
# ---------------------------------------------------------------------------


def _p_u8(buf: bytearray, v: int) -> None:
    buf += struct.pack(">B", v)


def _p_u16(buf: bytearray, v: int) -> None:
    buf += struct.pack(">H", v)


def _p_u32(buf: bytearray, v: int) -> None:
    buf += struct.pack(">I", v)


def _p_u64(buf: bytearray, v: int) -> None:
    buf += struct.pack(">Q", v)


def _p_bytes(buf: bytearray, b: bytes) -> None:
    _p_u32(buf, len(b))
    buf += b


def _p_str(buf: bytearray, s: str) -> None:
    _p_bytes(buf, s.encode("utf-8"))


class _Reader:
    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def take(self, n: int) -> bytes:
        if self.pos + n > len(self.buf):
            raise WireError("truncated payload")
        b = self.buf[self.pos:self.pos + n]
        self.pos += n
        return b

    def u8(self) -> int:
        return self.take(1)[0]

    def u16(self) -> int:
        return struct.unpack(">H", self.take(2))[0]

    def u32(self) -> int:
        return struct.unpack(">I", self.take(4))[0]

    def u64(self) -> int:
        return struct.unpack(">Q", self.take(8))[0]

    def bytes_(self) -> bytes:
        return self.take(self.u32())

    def str_(self) -> str:
        return self.bytes_().decode("utf-8")


# ---------------------------------------------------------------------------
# Pytree value codec
# ---------------------------------------------------------------------------


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _is_tensor(v: Any) -> bool:
    return isinstance(v, np.ndarray) or type(v).__module__.startswith(
        ("jax", "jaxlib"))


def _enc_tensor_header(buf: bytearray, dtype: str,
                       shape: Tuple[int, ...]) -> None:
    _p_str(buf, dtype)
    _p_u8(buf, len(shape))
    for d in shape:
        _p_u32(buf, d)


def _dec_tensor_header(r: _Reader) -> Tuple[str, Tuple[int, ...]]:
    dtype = r.str_()
    shape = tuple(r.u32() for _ in range(r.u8()))
    return dtype, shape


def encode_value(buf: bytearray, v: Any) -> None:
    """Canonical recursive pytree encoding (dict keys sorted)."""
    if isinstance(v, CompressedTree):
        _p_u8(buf, _T_CTREE)
        encode_value(buf, compressed_tree_to_structure(v))
    elif isinstance(v, CompressedLeaf):
        _p_u8(buf, _T_QLEAF)
        _enc_tensor_header(buf, v.dtype, tuple(v.shape))
        buf += np.float32(v.scale).tobytes()
        _p_bytes(buf, np.ascontiguousarray(v.q).tobytes())
    elif isinstance(v, dict):
        _p_u8(buf, _T_DICT)
        _p_u32(buf, len(v))
        for k in sorted(v):
            if not isinstance(k, str):
                raise WireError(f"dict keys must be str, got {type(k)}")
            _p_str(buf, k)
            encode_value(buf, v[k])
    elif isinstance(v, list):
        _p_u8(buf, _T_LIST)
        _p_u32(buf, len(v))
        for x in v:
            encode_value(buf, x)
    elif isinstance(v, tuple):
        _p_u8(buf, _T_TUPLE)
        _p_u32(buf, len(v))
        for x in v:
            encode_value(buf, x)
    elif isinstance(v, bool):               # before int (bool is int)
        _p_u8(buf, _T_BOOL)
        _p_u8(buf, 1 if v else 0)
    elif isinstance(v, int) and not isinstance(v, np.generic):
        _p_u8(buf, _T_INT)
        buf += struct.pack(">q", v)
    elif isinstance(v, float):
        _p_u8(buf, _T_FLOAT)
        buf += struct.pack(">d", v)
    elif isinstance(v, str):
        _p_u8(buf, _T_STR)
        _p_str(buf, v)
    elif v is None:
        _p_u8(buf, _T_NONE)
    elif _is_tensor(v) or isinstance(v, np.generic):
        a = np.asarray(v)
        _p_u8(buf, _T_TENSOR)
        _enc_tensor_header(buf, str(a.dtype), a.shape)
        _p_bytes(buf, np.ascontiguousarray(a).tobytes())
    else:
        raise WireError(f"unsupported payload value: {type(v)}")


def decode_value(r: _Reader) -> Any:
    tag = r.u8()
    if tag == _T_CTREE:
        return compressed_tree_from_structure(decode_value(r))
    if tag == _T_QLEAF:
        dtype, shape = _dec_tensor_header(r)
        scale = np.frombuffer(r.take(4), np.float32)[0]
        q = np.frombuffer(r.bytes_(), np.int8).reshape(shape).copy()
        return CompressedLeaf(q, scale, shape, dtype)
    if tag == _T_DICT:
        return {r.str_(): decode_value(r) for _ in range(r.u32())}
    if tag == _T_LIST:
        return [decode_value(r) for _ in range(r.u32())]
    if tag == _T_TUPLE:
        return tuple(decode_value(r) for _ in range(r.u32()))
    if tag == _T_TENSOR:
        dtype, shape = _dec_tensor_header(r)
        a = np.frombuffer(r.bytes_(), _np_dtype(dtype)).reshape(shape)
        import jax.numpy as jnp
        return jnp.asarray(a)
    if tag == _T_BOOL:
        return bool(r.u8())
    if tag == _T_INT:
        return struct.unpack(">q", r.take(8))[0]
    if tag == _T_FLOAT:
        return struct.unpack(">d", r.take(8))[0]
    if tag == _T_STR:
        return r.str_()
    if tag == _T_NONE:
        return None
    raise WireError(f"unknown value tag 0x{tag:02x}")


# ---------------------------------------------------------------------------
# Component codecs
# ---------------------------------------------------------------------------


# High bit of the adds count word marks the 4-string entry form that
# carries leaf coverage descriptors. A set with no sparse entries keeps
# the legacy 3-string encoding byte-for-byte (un-upgraded peers parse
# it); sparse entries append a 4th string — the \x1f-joined coverage
# paths, empty for dense entries riding in the same set.
_SPARSE_ADDS_FLAG = 0x80000000
_COVER_SEP = "\x1f"


def _enc_adds(buf: bytearray, adds: FrozenSet[AddEntry]) -> None:
    entries = sorted(adds)
    if len(entries) >= _SPARSE_ADDS_FLAG:
        raise WireError("too many add entries for one frame")
    sparse = any(e.leaf_paths is not None for e in entries)
    _p_u32(buf, len(entries) | (_SPARSE_ADDS_FLAG if sparse else 0))
    for e in entries:
        _p_str(buf, e.element_id)
        _p_str(buf, e.tag)
        _p_str(buf, e.node)
        if sparse:
            _p_str(buf, _COVER_SEP.join(e.leaf_paths)
                   if e.leaf_paths is not None else "")


def _dec_adds(r: _Reader) -> FrozenSet[AddEntry]:
    word = r.u32()
    n, sparse = word & ~_SPARSE_ADDS_FLAG, bool(word & _SPARSE_ADDS_FLAG)
    out = []
    for _ in range(n):
        eid, tag, node = r.str_(), r.str_(), r.str_()
        cover = None
        if sparse:
            raw = r.str_()
            if raw:
                cover = tuple(raw.split(_COVER_SEP))
        out.append(AddEntry(eid, tag, node, cover))
    return frozenset(out)


def _enc_removes(buf: bytearray, removes: FrozenSet[str]) -> None:
    _p_u32(buf, len(removes))
    for tag in sorted(removes):
        _p_str(buf, tag)


def _dec_removes(r: _Reader) -> FrozenSet[str]:
    return frozenset(r.str_() for _ in range(r.u32()))


def _enc_vv(buf: bytearray, vv: VersionVector) -> None:
    clocks = {k: v for k, v in vv.to_dict().items() if v}
    _p_u32(buf, len(clocks))
    for k in sorted(clocks):
        _p_str(buf, k)
        _p_u64(buf, clocks[k])


def _dec_vv(r: _Reader) -> VersionVector:
    return VersionVector({r.str_(): r.u64() for _ in range(r.u32())})


def _enc_payloads(buf: bytearray, payloads: Dict[str, Any]) -> None:
    _p_u32(buf, len(payloads))
    for eid in sorted(payloads):
        _p_str(buf, eid)
        encode_value(buf, payloads[eid])


def _dec_payloads(r: _Reader) -> Dict[str, Any]:
    return {r.str_(): decode_value(r) for _ in range(r.u32())}


def encode_layer1(adds: FrozenSet[AddEntry], removes: FrozenSet[str],
                  vv: VersionVector) -> bytes:
    """Canonical encoding of a Layer-1 (A, R, V) triple, payload-free.

    The exact add/remove/version-vector encoders the sync frames use
    (including the sparse `leaf_paths` coverage extension), exposed for
    the durable journal (`repro.core.journal`): WAL records and
    snapshots carry Layer-1 metadata in the same canonical bytes that
    cross the wire, so there is exactly one (de)serialization of
    `CRDTMergeState` metadata in the system."""
    buf = bytearray()
    _enc_adds(buf, adds)
    _enc_removes(buf, removes)
    _enc_vv(buf, vv)
    return bytes(buf)


def decode_layer1(raw: bytes) -> Tuple[FrozenSet[AddEntry],
                                       FrozenSet[str], VersionVector]:
    """Inverse of `encode_layer1`; raises `WireError` on malformed or
    trailing bytes (a durable record must parse exactly)."""
    r = _Reader(raw)
    adds = _dec_adds(r)
    removes = _dec_removes(r)
    vv = _dec_vv(r)
    if r.pos != len(raw):
        raise WireError("trailing bytes after layer-1 payload")
    return adds, removes, vv


# ---------------------------------------------------------------------------
# Message codecs
# ---------------------------------------------------------------------------


def _enc_state(buf: bytearray, m: StateMsg) -> None:
    _p_str(buf, m.sender)
    _enc_adds(buf, m.adds)
    _enc_removes(buf, m.removes)
    _enc_vv(buf, m.vv)
    _enc_payloads(buf, m.payloads)


def _dec_state(r: _Reader) -> StateMsg:
    return StateMsg(r.str_(), _dec_adds(r), _dec_removes(r), _dec_vv(r),
                    _dec_payloads(r))


def _enc_delta(buf: bytearray, m: DeltaMsg) -> None:
    _p_str(buf, m.sender)
    _p_u8(buf, 1 if m.compressed else 0)
    _enc_adds(buf, m.adds)
    _enc_removes(buf, m.removes)
    _enc_vv(buf, m.vv)
    _enc_payloads(buf, m.payloads)


def _dec_delta(r: _Reader) -> DeltaMsg:
    sender = r.str_()
    compressed = bool(r.u8())
    return DeltaMsg(sender, _dec_adds(r), _dec_removes(r), _dec_vv(r),
                    _dec_payloads(r), compressed)


def _enc_sync_req(buf: bytearray, m: SyncReq) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_bytes(buf, m.root)
    _p_u8(buf, m.bits)
    _enc_vv(buf, m.vv)


def _dec_sync_req(r: _Reader) -> SyncReq:
    return SyncReq(r.str_(), r.u64(), r.bytes_(), r.u8(), _dec_vv(r))


def _enc_buckets(buf: bytearray, m: BucketsMsg) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_u8(buf, m.bits)
    _p_u32(buf, len(m.digests))
    for idx in sorted(m.digests):
        _p_u16(buf, idx)
        _p_bytes(buf, m.digests[idx])


def _dec_buckets(r: _Reader) -> BucketsMsg:
    sender, sid, bits = r.str_(), r.u64(), r.u8()
    digests = {r.u16(): r.bytes_() for _ in range(r.u32())}
    return BucketsMsg(sender, sid, bits, digests)


def _enc_bucket_items(buf: bytearray, m: BucketItemsMsg) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_u8(buf, m.bits)
    _enc_adds(buf, m.adds)
    _enc_removes(buf, m.removes)
    _enc_vv(buf, m.vv)
    _p_u32(buf, len(m.want))
    for idx in sorted(m.want):
        _p_u16(buf, idx)


def _dec_bucket_items(r: _Reader) -> BucketItemsMsg:
    sender, sid, bits = r.str_(), r.u64(), r.u8()
    adds, removes, vv = _dec_adds(r), _dec_removes(r), _dec_vv(r)
    want = tuple(r.u16() for _ in range(r.u32()))
    return BucketItemsMsg(sender, sid, bits, adds, removes, vv, want)


def _enc_blob_req(buf: bytearray, m: BlobReq) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_u32(buf, len(m.eids))
    for eid in sorted(m.eids):
        _p_str(buf, eid)


def _dec_blob_req(r: _Reader) -> BlobReq:
    sender, sid = r.str_(), r.u64()
    eids = tuple(r.str_() for _ in range(r.u32()))
    return BlobReq(sender, sid, eids)


def _enc_blob_resp(buf: bytearray, m: BlobResp) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_u8(buf, 1 if m.compressed else 0)
    _enc_payloads(buf, m.payloads)


def _dec_blob_resp(r: _Reader) -> BlobResp:
    sender, sid = r.str_(), r.u64()
    compressed = bool(r.u8())
    return BlobResp(sender, sid, _dec_payloads(r), compressed)


def _enc_sync_done(buf: bytearray, m: SyncDone) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _enc_vv(buf, m.vv)


def _dec_sync_done(r: _Reader) -> SyncDone:
    return SyncDone(r.str_(), r.u64(), _dec_vv(r))


def _enc_blob_manifest(buf: bytearray, m: BlobManifest) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_u32(buf, len(m.entries))
    for e in sorted(m.entries, key=lambda x: x.eid):
        _p_str(buf, e.eid)
        _p_u64(buf, e.total_size)
        _p_u32(buf, e.chunk_size)
        _p_u32(buf, len(e.digests))
        for d in e.digests:
            if len(d) != DIGEST_LEN:
                raise WireError(f"chunk digest must be {DIGEST_LEN}B")
            buf += d


def _dec_blob_manifest(r: _Reader) -> BlobManifest:
    sender, sid = r.str_(), r.u64()
    entries = []
    for _ in range(r.u32()):
        eid, total, csize = r.str_(), r.u64(), r.u32()
        digests = tuple(r.take(DIGEST_LEN) for _ in range(r.u32()))
        entries.append(ManifestEntry(eid, csize, total, digests))
    return BlobManifest(sender, sid, tuple(entries))


def _enc_chunk_req(buf: bytearray, m: ChunkReq) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_str(buf, m.eid)
    _p_u32(buf, m.chunk_size)
    _p_u32(buf, len(m.indices))
    for i in sorted(m.indices):
        _p_u32(buf, i)


def _dec_chunk_req(r: _Reader) -> ChunkReq:
    sender, sid, eid, csize = r.str_(), r.u64(), r.str_(), r.u32()
    indices = tuple(r.u32() for _ in range(r.u32()))
    return ChunkReq(sender, sid, eid, csize, indices)


def _enc_chunk_data(buf: bytearray, m: ChunkData) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_str(buf, m.eid)
    _p_u32(buf, m.index)
    _p_bytes(buf, m.data)


def _dec_chunk_data(r: _Reader) -> ChunkData:
    return ChunkData(r.str_(), r.u64(), r.str_(), r.u32(), r.bytes_())


def _enc_have_req(buf: bytearray, m: HaveReq) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_u32(buf, len(set(m.eids)))
    for eid in sorted(set(m.eids)):
        _p_str(buf, eid)


def _dec_have_req(r: _Reader) -> HaveReq:
    sender, sid = r.str_(), r.u64()
    eids = tuple(r.str_() for _ in range(r.u32()))
    return HaveReq(sender, sid, eids)


def _enc_have_map(buf: bytearray, m: HaveMap) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_u32(buf, len(m.entries))
    for e in sorted(m.entries, key=lambda x: x.eid):
        if e.n_chunks == 0 and e.bitmap:
            raise WireError("complete HaveEntry must carry no bitmap")
        if e.n_chunks > 0 and len(e.bitmap) != (e.n_chunks + 7) // 8:
            raise WireError(f"HaveEntry bitmap must be "
                            f"{(e.n_chunks + 7) // 8}B for {e.n_chunks} "
                            f"chunks, got {len(e.bitmap)}B")
        _p_str(buf, e.eid)
        _p_u32(buf, e.n_chunks)
        if e.n_chunks:
            buf += e.bitmap


def _dec_have_map(r: _Reader) -> HaveMap:
    sender, sid = r.str_(), r.u64()
    entries = []
    for _ in range(r.u32()):
        eid, n = r.str_(), r.u32()
        bitmap = r.take((n + 7) // 8) if n else b""
        entries.append(HaveEntry(eid, n, bitmap))
    return HaveMap(sender, sid, tuple(entries))


def _enc_sparse_manifest(buf: bytearray, m: SparseManifest) -> None:
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_u32(buf, len(m.entries))
    for e in sorted(m.entries, key=lambda x: x.eid):
        me = e.manifest
        _p_str(buf, me.eid)
        _p_u64(buf, me.total_size)
        _p_u32(buf, me.chunk_size)
        _p_u32(buf, len(me.digests))
        for d in me.digests:
            if len(d) != DIGEST_LEN:
                raise WireError(f"chunk digest must be {DIGEST_LEN}B")
            buf += d
        _p_u32(buf, len(e.leaves))
        for l in e.leaves:
            if len(l.digest) != DIGEST_LEN:
                raise WireError(f"leaf digest must be {DIGEST_LEN}B")
            _p_str(buf, l.path)
            buf += l.digest
            _enc_tensor_header(buf, l.dtype, tuple(l.shape))
            # quantization trailer: u8 flag, then fp32 scale if set
            if l.scale is None:
                buf.append(0)
            else:
                buf.append(1)
                buf += struct.pack("<f", float(l.scale))


def _dec_sparse_manifest(r: _Reader) -> SparseManifest:
    sender, sid = r.str_(), r.u64()
    entries = []
    for _ in range(r.u32()):
        eid, total, csize = r.str_(), r.u64(), r.u32()
        digests = tuple(r.take(DIGEST_LEN) for _ in range(r.u32()))
        leaves = []
        for _ in range(r.u32()):
            path = r.str_()
            digest = r.take(DIGEST_LEN)
            dtype, shape = _dec_tensor_header(r)
            flag = r.take(1)[0]
            if flag not in (0, 1):
                raise WireError(f"bad leaf-ref scale flag {flag}")
            scale = (struct.unpack("<f", r.take(4))[0] if flag
                     else None)
            leaves.append(LeafRef(path, digest, dtype, shape, scale))
        entries.append(SparseManifestEntry(
            ManifestEntry(eid, csize, total, digests), tuple(leaves)))
    return SparseManifest(sender, sid, tuple(entries))


def _enc_resolve_spec(buf: bytearray, m: ResolveSpecMsg) -> None:
    from repro.api.spec import MergeSpec, SpecError
    if not isinstance(m.spec, MergeSpec):
        raise WireError(f"ResolveSpecMsg.spec must be a MergeSpec, "
                        f"got {type(m.spec).__name__}")
    try:
        raw = m.spec.encode()
        # full strict round-trip at ENCODE time: receivers reject any
        # spec that fails strict validation (array-valued cfg, lenient
        # specs with undeclared knobs, …), and a decode failure there
        # would abort the peer's whole delivery drain — refuse to emit
        # anything a well-behaved receiver must throw away
        MergeSpec.decode(raw)
    except (SpecError, KeyError) as e:
        raise WireError(f"MergeSpec not gossipable (a peer's strict "
                        f"decode would reject it): {e}") from e
    _p_str(buf, m.sender)
    _p_u64(buf, m.sid)
    _p_bytes(buf, raw)


def _dec_resolve_spec(r: _Reader) -> ResolveSpecMsg:
    from repro.api.spec import MergeSpec, SpecError
    sender, sid, raw = r.str_(), r.u64(), r.bytes_()
    try:
        spec = MergeSpec.decode(raw)
    except (SpecError, KeyError, ValueError, struct.error) as e:
        # strict validation applies on ingest: an unknown strategy or
        # undeclared cfg from a peer is a malformed frame, not a merge.
        # ValueError also covers non-numeric _V_INT payloads and
        # UnicodeDecodeError from corrupt strings — every parse failure
        # must surface as WireError so a hostile frame cannot abort the
        # receiver's delivery drain with a foreign exception type.
        raise WireError(f"bad MergeSpec payload: {e}") from e
    return ResolveSpecMsg(sender, sid, spec)


_ENCODERS = {
    MSG_STATE: _enc_state, MSG_DELTA: _enc_delta,
    MSG_SYNC_REQ: _enc_sync_req, MSG_BUCKETS: _enc_buckets,
    MSG_BUCKET_ITEMS: _enc_bucket_items, MSG_BLOB_REQ: _enc_blob_req,
    MSG_BLOB_RESP: _enc_blob_resp, MSG_SYNC_DONE: _enc_sync_done,
    MSG_BLOB_MANIFEST: _enc_blob_manifest, MSG_CHUNK_REQ: _enc_chunk_req,
    MSG_CHUNK_DATA: _enc_chunk_data, MSG_HAVE_REQ: _enc_have_req,
    MSG_HAVE_MAP: _enc_have_map, MSG_RESOLVE_SPEC: _enc_resolve_spec,
    MSG_SPARSE_MANIFEST: _enc_sparse_manifest,
}
_DECODERS = {
    MSG_STATE: _dec_state, MSG_DELTA: _dec_delta,
    MSG_SYNC_REQ: _dec_sync_req, MSG_BUCKETS: _dec_buckets,
    MSG_BUCKET_ITEMS: _dec_bucket_items, MSG_BLOB_REQ: _dec_blob_req,
    MSG_BLOB_RESP: _dec_blob_resp, MSG_SYNC_DONE: _dec_sync_done,
    MSG_BLOB_MANIFEST: _dec_blob_manifest, MSG_CHUNK_REQ: _dec_chunk_req,
    MSG_CHUNK_DATA: _dec_chunk_data, MSG_HAVE_REQ: _dec_have_req,
    MSG_HAVE_MAP: _dec_have_map, MSG_RESOLVE_SPEC: _dec_resolve_spec,
    MSG_SPARSE_MANIFEST: _dec_sparse_manifest,
}

# Public registry: every frame tag the codec accepts, with its message
# class. docs/PROTOCOL.md's frame table is diffed against this in
# tests/test_docs.py, so the spec cannot drift from the implementation.
MESSAGE_TYPES: Dict[int, type] = {
    MSG_STATE: StateMsg, MSG_DELTA: DeltaMsg, MSG_SYNC_REQ: SyncReq,
    MSG_BUCKETS: BucketsMsg, MSG_BUCKET_ITEMS: BucketItemsMsg,
    MSG_BLOB_REQ: BlobReq, MSG_BLOB_RESP: BlobResp,
    MSG_SYNC_DONE: SyncDone, MSG_BLOB_MANIFEST: BlobManifest,
    MSG_CHUNK_REQ: ChunkReq, MSG_CHUNK_DATA: ChunkData,
    MSG_HAVE_REQ: HaveReq, MSG_HAVE_MAP: HaveMap,
    MSG_RESOLVE_SPEC: ResolveSpecMsg,
    MSG_SPARSE_MANIFEST: SparseManifest,
}


# ---------------------------------------------------------------------------
# Framing
# ---------------------------------------------------------------------------


_V2_TYPES = frozenset({MSG_HAVE_REQ, MSG_HAVE_MAP, MSG_RESOLVE_SPEC,
                       MSG_SPARSE_MANIFEST})


def frame_version(mtype: int) -> int:
    """The version stamp a frame of `mtype` carries (see HEADER note)."""
    return 2 if mtype in _V2_TYPES else 1


def encode_message(msg: Message) -> bytes:
    """Message dataclass -> framed bytes."""
    mtype = getattr(msg, "type", None)
    enc = _ENCODERS.get(mtype)
    if enc is None:
        raise WireError(f"not a wire message: {type(msg)}")
    payload = bytearray()
    enc(payload, msg)
    return (HEADER.pack(MAGIC, frame_version(mtype), mtype, len(payload))
            + bytes(payload)
            + TRAILER.pack(zlib.crc32(bytes(payload)) & 0xFFFFFFFF))


def decode_frame(buf: bytes, pos: int = 0) -> Tuple[Message, int]:
    """Decode one frame starting at `pos`; returns (message, next_pos).

    Validates magic, version, length, and checksum; raises WireError on
    any mismatch so corrupted frames are rejected, never half-applied.
    """
    if len(buf) - pos < HEADER.size:
        raise WireError("truncated header")
    magic, version, mtype, plen = HEADER.unpack_from(buf, pos)
    if magic != MAGIC:
        raise WireError(f"bad magic {magic!r}")
    if version not in ACCEPTED_VERSIONS:
        raise WireError(f"unsupported wire version {version}")
    body_start = pos + HEADER.size
    body_end = body_start + plen
    if len(buf) < body_end + TRAILER.size:
        raise WireError("truncated frame")
    payload = buf[body_start:body_end]
    (crc,) = TRAILER.unpack_from(buf, body_end)
    if crc != (zlib.crc32(payload) & 0xFFFFFFFF):
        raise WireError("checksum mismatch")
    dec = _DECODERS.get(mtype)
    if dec is None:
        raise WireError(f"unknown message type 0x{mtype:02x}")
    r = _Reader(payload)
    msg = dec(r)
    if r.pos != len(payload):
        raise WireError(f"{len(payload) - r.pos} trailing payload bytes")
    return msg, body_end + TRAILER.size


def decode_message(buf: bytes) -> Message:
    """Decode exactly one frame occupying the whole buffer."""
    msg, end = decode_frame(buf)
    if end != len(buf):
        raise WireError(f"{len(buf) - end} trailing bytes after frame")
    return msg


def frame_size(msg: Message) -> int:
    return len(encode_message(msg))


# ---------------------------------------------------------------------------
# Standalone blob (payload value) codec — the unit of chunked transfer
# ---------------------------------------------------------------------------


def encode_blob(value: Any) -> bytes:
    """Canonical bytes of one store payload (chunk digests cover these)."""
    buf = bytearray()
    encode_value(buf, value)
    return bytes(buf)


def decode_blob(blob: bytes) -> Any:
    r = _Reader(blob)
    value = decode_value(r)
    if r.pos != len(blob):
        raise WireError(f"{len(blob) - r.pos} trailing blob bytes")
    return value


def chunk_digests(blob: bytes, chunk_size: int) -> Tuple[bytes, ...]:
    """Per-chunk SHA-256 over `blob` split at `chunk_size` boundaries."""
    if chunk_size <= 0:
        raise WireError("chunk_size must be positive")
    return tuple(hashlib.sha256(blob[i:i + chunk_size]).digest()
                 for i in range(0, len(blob), chunk_size))


def manifest_entry(eid: str, blob: bytes, chunk_size: int) -> ManifestEntry:
    return ManifestEntry(eid, chunk_size, len(blob),
                         chunk_digests(blob, chunk_size))


def leaf_refs(payload: Any) -> Tuple[LeafRef, ...]:
    """Per-leaf planner refs of a payload pytree, sorted by path (the
    canonical coverage order).

    Quantized payloads (`CompressedTree`) produce scale-carrying refs:
    digests are computed on a transient per-leaf dequantization (one
    leaf live at a time — the full fp32 tree is never materialized),
    and the announced dtype/shape describe the dequantized tensor the
    receiver's planner will key against."""
    import jax
    from repro.core.hashing import tensor_digest
    if isinstance(payload, CompressedTree):
        payload = compressed_tree_to_structure(payload)
    is_q = lambda x: isinstance(x, CompressedLeaf)  # noqa: E731
    flat, _ = jax.tree_util.tree_flatten_with_path(payload, is_leaf=is_q)
    refs = []
    for p, leaf in flat:
        if is_q(leaf):
            dense = np.asarray(
                (leaf.q.astype(np.float32) * leaf.scale).reshape(
                    leaf.shape), leaf.dtype)
            refs.append(LeafRef(jax.tree_util.keystr(p),
                                tensor_digest(dense), str(dense.dtype),
                                tuple(dense.shape), float(leaf.scale)))
        else:
            refs.append(LeafRef(jax.tree_util.keystr(p),
                                tensor_digest(leaf),
                                str(np.asarray(leaf).dtype),
                                tuple(np.asarray(leaf).shape)))
    return tuple(sorted(refs, key=lambda r: r.path))


def sparse_manifest_entry(eid: str, payload: Any, blob: bytes,
                          chunk_size: int) -> SparseManifestEntry:
    """Leaf-level announcement of one contribution: chunking manifest of
    its canonical blob encoding + one LeafRef per carried leaf."""
    return SparseManifestEntry(manifest_entry(eid, blob, chunk_size),
                               leaf_refs(payload))


# ---------------------------------------------------------------------------
# State/Delta conversions
# ---------------------------------------------------------------------------


def state_to_msg(state: CRDTMergeState, sender: str) -> StateMsg:
    return StateMsg(sender, state.adds, state.removes, state.vv,
                    dict(state.store))


def msg_to_state(msg: StateMsg, *,
                 keep_quantized: bool = False) -> CRDTMergeState:
    # Compressed blobs decompress on arrival by default: the store then
    # holds the dequantized wire-format tensors (content identity,
    # Assumption 11). `keep_quantized=True` (SyncNode opt-in) stores the
    # CompressedTree as-is — the merge engine plans from the int8
    # payloads and densifies each slice it reads, and content identity is
    # unchanged because digests are always computed on dequantized
    # values.
    if keep_quantized:
        store = dict(msg.payloads)
    else:
        store = {eid: (decompress_tree(p) if isinstance(p, CompressedTree)
                       else p)
                 for eid, p in msg.payloads.items()}
    return CRDTMergeState(msg.adds, msg.removes, msg.vv, store)


def delta_to_msg(delta: Delta, sender: str) -> DeltaMsg:
    return DeltaMsg(sender, delta.adds, delta.removes, delta.vv,
                    dict(delta.payloads), delta.compressed)


def msg_to_delta(msg: DeltaMsg) -> Delta:
    return Delta(msg.adds, msg.removes, msg.vv, dict(msg.payloads),
                 msg.compressed)
