"""The plain reference of a sealed volume's `.ecx`: the index an EC volume
is read through (a GET finds its needle's offset and size there, by
binary search over the keys).

Nothing here imports the program. A volume's `.idx` is its append log of
16-byte records, big-endian: the needle's key (8 bytes), its offset in
the `.dat` in units of 8 bytes (4 bytes), its size (4 bytes). A delete
appends a record of the same key with the size 0xFFFFFFFF; a record with
offset 0 names nothing either. The `.ecx` SeaweedFS v1.71 writes beside
the shards (weed/storage/erasure_coding/ec_encoder.go
WriteSortedFileFromIdx, through needle_map.MemDb) is what the log leaves
when it is read in order: a later record of a key replaces the earlier, a
tombstone or a zero offset removes the key; then one 16-byte record a
live key, ascending by key.
"""

import hashlib
import struct

RECORD = struct.Struct(">QII")
TOMBSTONE = 0xFFFFFFFF


def ecx_bytes(idx_path: str) -> bytes:
    live = {}
    with open(idx_path, "rb") as f:
        log = f.read()
    for at in range(0, len(log) - RECORD.size + 1, RECORD.size):
        key, offset, size = RECORD.unpack_from(log, at)
        if size == TOMBSTONE or offset == 0:
            live.pop(key, None)
        else:
            live[key] = (offset, size)
    return b"".join(RECORD.pack(key, *live[key]) for key in sorted(live))


def ecx_account(idx_path: str) -> dict:
    """What the comparison needs: the sha256 of the `.ecx` the log must
    leave, and how many entries it has."""
    ecx = ecx_bytes(idx_path)
    return {"sha256": hashlib.sha256(ecx).hexdigest(),
            "entries": len(ecx) // RECORD.size, "bytes": len(ecx)}
