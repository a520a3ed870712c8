"""ctypes bindings for the native KvStore engine (native/kvstore).

The C++ library owns the key->Value table and runs the CRDT merge
(mergeKeyValues semantics, openr/kvstore/KvStore.cpp:261-411) natively;
Python keeps the protocol machinery (flooding, sync FSM, TTL timers) and
sees the table through `NativeKvTable`, a MutableMapping adapter speaking
the compact record format documented in native/kvstore/onl_kvstore.h.

Builds openr_tpu/_native/libopenr_kv.so through `make` on first use (a
no-op when the binary is fresh). `native_kv_available()` gates callers;
everything falls back to the pure-Python dict store when the toolchain is
missing.
"""

from __future__ import annotations

import ctypes
import logging
import struct
from typing import Dict, Iterator, MutableMapping, Optional, Tuple

from openr_tpu.types import KeyVals, Value, generate_hash
from openr_tpu.utils.native_build import build_native

log = logging.getLogger(__name__)

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    try:
        lib = ctypes.CDLL(build_native("libopenr_kv.so"))
    except Exception as exc:
        log.warning(
            "native KvStore engine unavailable (%r); dict store serves", exc
        )
        return None
    lib.okv_create.restype = ctypes.c_void_p
    lib.okv_destroy.argtypes = [ctypes.c_void_p]
    lib.okv_merge.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.okv_get.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.okv_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]
    lib.okv_erase.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.okv_size.argtypes = [ctypes.c_void_p]
    lib.okv_size.restype = ctypes.c_size_t
    lib.okv_dump.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.okv_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    _lib = lib
    return _lib


def native_kv_available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# record (de)serialization — mirrors native/kvstore/onl_kvstore.h
# ---------------------------------------------------------------------------


def _pack_record(key: str, v: Value) -> bytes:
    kb = key.encode()
    ob = v.originator_id.encode()
    parts = [struct.pack("<I", len(kb)), kb, struct.pack("<q", v.version)]
    parts += [struct.pack("<I", len(ob)), ob]
    if v.value is not None:
        parts += [b"\x01", struct.pack("<I", len(v.value)), v.value]
    else:
        parts += [b"\x00"]
    parts += [struct.pack("<q", v.ttl), struct.pack("<q", v.ttl_version)]
    if v.hash is not None:
        parts += [b"\x01", struct.pack("<q", v.hash)]
    else:
        parts += [b"\x00"]
    return b"".join(parts)


def _pack_records(key_vals: KeyVals) -> bytes:
    body = b"".join(_pack_record(k, v) for k, v in key_vals.items())
    return struct.pack("<I", len(key_vals)) + body


# hard ceilings on decoded record fields: a truncated or bit-flipped
# buffer must fail typed, not blind-slice garbage into the table
_MAX_KEY_BYTES = 8192
_MAX_VALUE_BYTES = 16 * 1024 * 1024
_MAX_RECORD_COUNT = 4 * 1024 * 1024


class NativeDecodeError(ValueError):
    """Typed rejection of a corrupt native record buffer.

    kind ∈ {"oversized", "truncated", "malformed"} — same counter mapping
    as wire.WireDecodeError (kvstore.wire.rejected.{kind})."""

    def __init__(self, kind: str, detail: str = "") -> None:
        super().__init__(f"{kind}: {detail}" if detail else kind)
        self.kind = kind


def _take(buf: bytes, off: int, n: int) -> int:
    """Bounds-check a read of n bytes at off; return the new offset."""
    if n < 0 or off + n > len(buf):
        raise NativeDecodeError(
            "truncated", f"need {n}B at offset {off}, have {len(buf)}B total"
        )
    return off + n


def _unpack_records(buf: bytes) -> KeyVals:
    end = _take(buf, 0, 4)
    (count,) = struct.unpack_from("<I", buf, 0)
    if count > _MAX_RECORD_COUNT:
        raise NativeDecodeError("oversized", f"{count} records")
    off = end
    out: KeyVals = {}
    for _ in range(count):
        end = _take(buf, off, 4)
        (klen,) = struct.unpack_from("<I", buf, off)
        if klen > _MAX_KEY_BYTES:
            raise NativeDecodeError("oversized", f"key {klen}B")
        off = _take(buf, end, klen)
        try:
            key = buf[end:off].decode()
        except UnicodeDecodeError as exc:
            raise NativeDecodeError("malformed", "key not utf-8") from exc
        end = _take(buf, off, 8)
        (version,) = struct.unpack_from("<q", buf, off)
        off = _take(buf, end, 4)
        (olen,) = struct.unpack_from("<I", buf, end)
        if olen > _MAX_KEY_BYTES:
            raise NativeDecodeError("oversized", f"originator {olen}B")
        end = _take(buf, off, olen)
        try:
            orig = buf[off:end].decode()
        except UnicodeDecodeError as exc:
            raise NativeDecodeError(
                "malformed", "originator not utf-8"
            ) from exc
        off = _take(buf, end, 1)
        has_value = buf[end]
        if has_value not in (0, 1):
            raise NativeDecodeError("malformed", "bad value-present flag")
        value = None
        if has_value:
            end = _take(buf, off, 4)
            (vlen,) = struct.unpack_from("<I", buf, off)
            if vlen > _MAX_VALUE_BYTES:
                raise NativeDecodeError("oversized", f"value {vlen}B")
            off = _take(buf, end, vlen)
            value = bytes(buf[end:off])
        end = _take(buf, off, 16)
        ttl, ttl_version = struct.unpack_from("<qq", buf, off)
        off = _take(buf, end, 1)
        has_hash = buf[end]
        if has_hash not in (0, 1):
            raise NativeDecodeError("malformed", "bad hash-present flag")
        hash_ = None
        if has_hash:
            end = _take(buf, off, 8)
            (hash_,) = struct.unpack_from("<q", buf, off)
            off = end
        out[key] = Value(version, orig, value, ttl, ttl_version, hash_)
    return out


def _call_out(fn, *args) -> bytes:
    """Invoke a C function with trailing (uint8_t**, size_t*) outputs."""
    lib = _load()
    assert lib is not None
    out = ctypes.POINTER(ctypes.c_uint8)()
    out_len = ctypes.c_size_t()
    rc = fn(*args, ctypes.byref(out), ctypes.byref(out_len))
    if rc < 0:
        raise RuntimeError("native kvstore: malformed buffer")
    try:
        return ctypes.string_at(out, out_len.value)
    finally:
        lib.okv_free(out)


# ---------------------------------------------------------------------------
# the table adapter
# ---------------------------------------------------------------------------


class NativeKvTable(MutableMapping):
    """Mapping view over a native store handle.

    KvStoreDb treats its store as Dict[str, Value]; this adapter satisfies
    that contract while keeping the records (and the merge hot path) in
    C++. `native_merge` is the fast path `merge_key_values` dispatches to.
    """

    def __init__(self) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError("native kvstore library unavailable")
        self._lib = lib
        self._h = lib.okv_create()

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h:
            self._lib.okv_destroy(h)
            self._h = None

    # -- mapping protocol --------------------------------------------------

    def __getitem__(self, key: str) -> Value:
        kb = key.encode()
        buf = _call_out(self._lib.okv_get, self._h, kb, len(kb))
        records = _unpack_records(buf)
        if not records:
            raise KeyError(key)
        return records[key]

    def __setitem__(self, key: str, value: Value) -> None:
        rec = _pack_record(key, value)
        if self._lib.okv_set(self._h, rec, len(rec)) != 0:
            raise RuntimeError("native kvstore: set failed")

    def __delitem__(self, key: str) -> None:
        kb = key.encode()
        if not self._lib.okv_erase(self._h, kb, len(kb)):
            raise KeyError(key)

    def __contains__(self, key: object) -> bool:
        try:
            self[key]  # type: ignore[index]
            return True
        except KeyError:
            return False

    def __len__(self) -> int:
        return self._lib.okv_size(self._h)

    def __iter__(self) -> Iterator[str]:
        return iter(self._snapshot())

    def items(self):
        return self._snapshot().items()

    def values(self):
        return self._snapshot().values()

    def _snapshot(self) -> Dict[str, Value]:
        return _unpack_records(_call_out(self._lib.okv_dump, self._h))

    # -- merge fast path ---------------------------------------------------

    def native_merge(self, key_vals: KeyVals, filters=None) -> KeyVals:
        """CRDT merge in C++; same contract as merge_key_values."""
        to_merge: KeyVals = {}
        for key, value in key_vals.items():
            if filters is not None and not filters.key_match(key, value):
                continue
            if value.value is not None and value.hash is None:
                # reference computes the hash at the originator
                # (mergeKeyValues fills it before storing); pre-fill so the
                # engine only compares
                value = value.copy()
                value.hash = generate_hash(
                    value.version, value.originator_id, value.value
                )
            to_merge[key] = value
        if not to_merge:
            return {}
        buf = _pack_records(to_merge)
        out = _call_out(self._lib.okv_merge, self._h, buf, len(buf))
        (count,) = struct.unpack_from("<I", out, 0)
        off = 4
        updates: KeyVals = {}
        for _ in range(count):
            (klen,) = struct.unpack_from("<I", out, off)
            off += 4
            key = out[off:off + klen].decode()
            off += klen
            updates[key] = to_merge[key]
        return updates
