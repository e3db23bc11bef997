"""Length-prefixed wire format for exported KV pages.

A handoff blob carries a request's page-aligned prompt KV exactly as
it sits in the sending pool: the storage leaves byte for byte (bf16
values, or int8/fp8 values plus their float32 per-page scale rows, plus
the draft-model leaves when the sender speculates).  The receiver
installs the bytes verbatim — **never** re-quantizes — so a migrated
page is bit-identical to the page the sender prefilled, and the
decode replica's bitwise chunked-prefill contract extends across the
hop (tests/test_handoff.py round-trips every kv_dtype).

Layout (all integers little-endian):

=========  ==============================================================
bytes      content
=========  ==============================================================
8          magic ``b"MLTKV1\\0\\n"``
8          u64 — JSON header length ``H``
H          UTF-8 JSON header: ``{"version", "kv_dtype", "page_size",
           "tokens", "leaves": [{"name", "dtype", "shape"}, ...]}``
per leaf   u64 byte length, then the leaf's raw C-order bytes, in
           header order
=========  ==============================================================

``tokens`` is the page-aligned token prefix the pages hold (length ==
``n_pages * page_size``) — the receiving :class:`PrefixCache` keys its
trie nodes on exactly these ids.  Leaves are LOGICAL, whatever row the
pool stores (ops/kv_quant.kv_to_leaves): ``k``/``v``/``draft_k``/
``draft_v``, each ``[L, n_pages, page, nkv, d]``, with ``.q`` /
``.scale`` (``[L, n_pages, nkv]``) suffixes for quantized pools; every
leaf's page axis is axis 1.
"""

from __future__ import annotations

import json
import struct
from typing import Dict, List, NamedTuple, Sequence

import ml_dtypes
import numpy as np

MAGIC = b"MLTKV1\0\n"
_U64 = struct.Struct("<Q")

# dtype names that appear on the wire; ml_dtypes (a jax dependency)
# registers the non-standard ones with numpy
_EXTENDED_DTYPES = {
    "bfloat16": ml_dtypes.bfloat16,
    "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
    "float8_e5m2": ml_dtypes.float8_e5m2,
}


def _np_dtype(name: str) -> np.dtype:
    return np.dtype(_EXTENDED_DTYPES.get(name, name))


class HandoffPayload(NamedTuple):
    """A decoded handoff blob: the trie key tokens + the raw leaves."""

    tokens: List[int]
    page_size: int
    kv_dtype: str
    leaves: Dict[str, np.ndarray]

    @property
    def n_pages(self) -> int:
        return len(self.tokens) // self.page_size if self.page_size else 0


def encode_pages(tokens: Sequence[int], page_size: int, kv_dtype: str,
                 leaves: Dict[str, np.ndarray]) -> bytes:
    """Serialize exported page leaves into one handoff blob.

    ``tokens`` must be page-aligned (the full pages' token ids) and
    every leaf's page axis (axis 1) must hold ``len(tokens) //
    page_size`` pages — the invariants the receiver's trie insert
    depends on, checked here so a malformed export fails at the sender.
    """
    tokens = [int(t) for t in tokens]
    if page_size <= 0:
        raise ValueError("page_size must be positive")
    if len(tokens) % page_size != 0:
        raise ValueError(
            f"tokens not page-aligned: {len(tokens)} ids, page {page_size}")
    n_pages = len(tokens) // page_size
    header = {
        "version": 1,
        "kv_dtype": str(kv_dtype),
        "page_size": int(page_size),
        "tokens": tokens,
        "leaves": [],
    }
    blocks: List[bytes] = []
    for name, arr in leaves.items():
        arr = np.ascontiguousarray(arr)
        if arr.ndim < 2 or arr.shape[1] != n_pages:
            raise ValueError(
                f"leaf {name!r} holds {arr.shape[1] if arr.ndim > 1 else 0} "
                f"pages on axis 1, expected {n_pages}")
        header["leaves"].append({
            "name": str(name),
            "dtype": str(arr.dtype),
            "shape": [int(s) for s in arr.shape],
        })
        blocks.append(arr.tobytes())
    hj = json.dumps(header, separators=(",", ":")).encode("utf-8")
    out = [MAGIC, _U64.pack(len(hj)), hj]
    for b in blocks:
        out.append(_U64.pack(len(b)))
        out.append(b)
    return b"".join(out)


def decode_pages(blob: bytes) -> HandoffPayload:
    """Parse a handoff blob back into its token key + leaf arrays.

    Every structural claim the header makes (magic, version, lengths,
    per-leaf shape x dtype vs. block size) is validated before any
    array is built — the decode replica calls this on bytes from the
    network."""
    if len(blob) < len(MAGIC) + _U64.size or blob[:len(MAGIC)] != MAGIC:
        raise ValueError("not a KV handoff blob (bad magic)")
    off = len(MAGIC)
    (hlen,) = _U64.unpack_from(blob, off)
    off += _U64.size
    if off + hlen > len(blob):
        raise ValueError("truncated handoff header")
    header = json.loads(blob[off:off + hlen].decode("utf-8"))
    off += hlen
    if header.get("version") != 1:
        raise ValueError(f"unsupported handoff version {header.get('version')}")
    page_size = int(header["page_size"])
    tokens = [int(t) for t in header["tokens"]]
    if page_size <= 0 or len(tokens) % page_size != 0:
        raise ValueError("handoff header tokens not page-aligned")
    n_pages = len(tokens) // page_size
    leaves: Dict[str, np.ndarray] = {}
    for spec in header["leaves"]:
        if off + _U64.size > len(blob):
            raise ValueError("truncated handoff leaf table")
        (blen,) = _U64.unpack_from(blob, off)
        off += _U64.size
        if off + blen > len(blob):
            raise ValueError(f"truncated handoff leaf {spec.get('name')!r}")
        dtype = _np_dtype(spec["dtype"])
        shape = tuple(int(s) for s in spec["shape"])
        if len(shape) < 2 or shape[1] != n_pages:
            raise ValueError(
                f"leaf {spec.get('name')!r} shape {shape} does not hold "
                f"{n_pages} pages on axis 1")
        expect = int(np.prod(shape)) * dtype.itemsize
        if expect != blen:
            raise ValueError(
                f"leaf {spec.get('name')!r}: {blen} bytes on the wire, "
                f"shape x dtype needs {expect}")
        arr = np.frombuffer(blob, dtype=dtype, count=int(np.prod(shape)),
                            offset=off).reshape(shape)
        leaves[str(spec["name"])] = arr
        off += blen
    if off != len(blob):
        raise ValueError(f"{len(blob) - off} trailing bytes in handoff blob")
    return HandoffPayload(tokens=tokens, page_size=page_size,
                          kv_dtype=str(header["kv_dtype"]), leaves=leaves)
