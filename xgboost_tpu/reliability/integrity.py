"""Crash-safe, integrity-checked file I/O for persisted models.

Two guarantees (RELIABILITY.md):

1. **No torn destination files.**  :func:`atomic_write` stages into a
   same-directory temp file, flushes + fsyncs it, ``os.replace``-s over
   the destination, and fsyncs the directory — a crash at ANY point
   leaves either the complete old file or the complete new file, never
   a prefix.
2. **No silent corruption.**  Every model file written through
   :func:`add_footer` carries a fixed-length ASCII CRC32 footer::

       \\nXGTPUCRC1 <crc32:08x> <payload_len:016d>\\n

   :func:`verify_model_bytes` strips and checks it, raising the typed
   :class:`ModelIntegrityError` on torn or bit-flipped content.  The
   footer is ASCII so the text-safe ``bs64`` model encoding stays
   text-safe, and it is appended AFTER the payload so readers strip it
   before parsing.  Files without a footer (pre-reliability saves,
   reference-format models) load with a one-time warning — backward
   compatible, just unverified.

Both functions route through :mod:`~xgboost_tpu.reliability.faults`
seams, so chaos tests corrupt/starve the REAL write and read paths.
"""

from __future__ import annotations

import contextlib
import os
import re
import sys
import tempfile
import zlib
from typing import Union

from xgboost_tpu.reliability import faults

FOOTER_MAGIC = b"XGTPUCRC1"
# \n + magic(9) + sp + crc(8 hex) + sp + len(16 dec) + \n
FOOTER_LEN = 1 + 9 + 1 + 8 + 1 + 16 + 1
_FOOTER_RE = re.compile(rb"\nXGTPUCRC1 ([0-9a-f]{8}) (\d{16})\n\Z")


class ModelIntegrityError(ValueError):
    """A persisted model failed verification (torn, truncated, or
    bit-flipped).  Subclasses ``ValueError`` so pre-reliability callers
    that caught generic parse errors keep working."""


def make_footer(payload: bytes) -> bytes:
    crc = zlib.crc32(payload) & 0xFFFFFFFF
    return b"\n%s %08x %016d\n" % (FOOTER_MAGIC, crc, len(payload))


def add_footer(payload: bytes) -> bytes:
    """Payload + CRC32 footer (what every model writer persists)."""
    return payload + make_footer(payload)


def has_footer(raw: bytes) -> bool:
    return _FOOTER_RE.search(raw) is not None


_warned_unverified = set()


def verify_model_bytes(raw: bytes, name: str = "<buffer>",
                       warn: bool = True) -> bytes:
    """Verify + strip the CRC footer, returning the payload.

    Raises :class:`ModelIntegrityError` when the footer is present but
    wrong (bit flip), truncated mid-footer (torn write), or the length
    disagrees.  Footer-less files return unchanged with a one-time
    warning per name — pre-reliability and reference-format models stay
    loadable, just unverified."""
    m = _FOOTER_RE.search(raw)
    if m is None:
        # a torn write can cut INSIDE the footer: payload bytes intact
        # but the verification record mangled — that is corruption, not
        # a legacy file.  Two tells: the full magic somewhere in the
        # tail (cut after the magic), or the file ENDING with a proper
        # prefix of the footer (cut inside the magic itself)
        head = b"\n" + FOOTER_MAGIC + b" "
        torn_prefix = any(raw.endswith(head[:k])
                          for k in range(2, len(head)))
        if torn_prefix or FOOTER_MAGIC in raw[-(FOOTER_LEN + 8):]:
            _count_integrity_failure(name, "truncated footer (torn write)")
            raise ModelIntegrityError(
                f"{name}: truncated integrity footer (torn write)")
        if warn and name not in _warned_unverified:
            _warned_unverified.add(name)
            print(f"[integrity] {name}: no integrity footer "
                  "(pre-reliability or reference file); loading "
                  "unverified", file=sys.stderr)
        return raw
    payload = raw[:-FOOTER_LEN]
    want_crc, want_len = int(m.group(1), 16), int(m.group(2))
    if len(payload) != want_len:
        _count_integrity_failure(name, "length mismatch (torn write)")
        raise ModelIntegrityError(
            f"{name}: payload is {len(payload)} bytes, footer says "
            f"{want_len} (torn write)")
    got_crc = zlib.crc32(payload) & 0xFFFFFFFF
    if got_crc != want_crc:
        _count_integrity_failure(name, "CRC32 mismatch")
        raise ModelIntegrityError(
            f"{name}: CRC32 mismatch (footer {want_crc:08x}, content "
            f"{got_crc:08x}) — bit flip or partial overwrite")
    return payload


def _count_integrity_failure(name: str = "<buffer>",
                             reason: str = "") -> None:
    from xgboost_tpu.obs import event
    from xgboost_tpu.obs import reliability_metrics
    reliability_metrics().integrity_failures.inc()
    event("integrity.failure", file=name, reason=reason)


@contextlib.contextmanager
def atomic_writer(path: Union[str, os.PathLike], durable: bool = True):
    """Context manager yielding a binary file object staged in the
    destination directory; a clean exit flushes, fsyncs, ``os.replace``-s
    it over ``path`` and fsyncs the directory — :func:`atomic_write`
    for writers that STREAM (an npz archive bigger than RAM headroom
    must not be staged in memory first).  An exception unlinks the
    temp file and leaves the destination untouched.

    Streamed bytes bypass the ``faults.mutate_write`` chaos seam (it
    needs the whole payload); whole-payload writers should use
    :func:`atomic_write`."""
    path = os.fspath(path)
    d = os.path.dirname(os.path.abspath(path))
    # mkstemp creates 0600; a plain open(path, "wb") would have given
    # 0666&~umask (and overwriting keeps the old mode) — preserve that
    # contract so a reader under another uid/gid doesn't lose access
    try:
        mode = os.stat(path).st_mode & 0o777
    except OSError:
        mask = os.umask(0)
        os.umask(mask)
        mode = 0o666 & ~mask
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            yield f
            f.flush()
            os.fchmod(f.fileno(), mode)
            if durable:
                os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    if durable:
        dfd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)


def atomic_write(path: Union[str, os.PathLike], data: bytes,
                 durable: bool = True) -> None:
    """Crash-safe whole-file write: tmp file in the destination
    directory -> flush -> fsync -> ``os.replace`` -> directory fsync.
    ``durable=False`` skips the fsyncs (scratch files, tests)."""
    path = os.fspath(path)
    data = faults.mutate_write(path, data)
    with atomic_writer(path, durable=durable) as f:
        f.write(data)


def read_file(path: Union[str, os.PathLike]) -> bytes:
    """Whole-file read through the fault seam (slow_read/read_flip)."""
    path = os.fspath(path)
    with open(path, "rb") as f:
        raw = f.read()
    return faults.mutate_read(path, raw)


def quarantine(path: Union[str, os.PathLike]) -> str:
    """Move a corrupt file aside as ``<path>.corrupt`` (numbered when
    that exists) so retry loops stop re-reading it and a post-mortem
    can inspect the bytes.  Returns the quarantine path."""
    path = os.fspath(path)
    dest = path + ".corrupt"
    i = 1
    while os.path.exists(dest):
        dest = f"{path}.corrupt{i}"
        i += 1
    os.replace(path, dest)
    # same dir-fsync discipline as atomic_write: the rename must be
    # durable before the next ring scan trusts it — a crash straight
    # after an unfsynced quarantine can resurrect the corrupt member
    # under its original name and send the scan into the same bytes
    dfd = os.open(os.path.dirname(os.path.abspath(dest)), os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)
    from xgboost_tpu.obs import event
    from xgboost_tpu.obs import reliability_metrics
    reliability_metrics().quarantines.inc()
    event("integrity.quarantine", file=path, quarantined_as=dest)
    return dest
