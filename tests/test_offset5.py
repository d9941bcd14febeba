"""5-byte offsets / >32GB volumes (reference
types/offset_5bytes.go — a build tag there, a per-volume superblock flag
here). Sparse files keep these tests fast: the needles live beyond the
32GB line without writing 32GB of zeros."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.ec.decoder import read_ec_volume_superblock, \
    write_idx_file_from_ec_index
from seaweedfs_tpu.ec.ec_volume import EcVolume
from seaweedfs_tpu.ec.encoder import write_sorted_file_from_idx
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.needle_map import walk_index_file
from seaweedfs_tpu.storage.super_block import FLAG_5_BYTE_OFFSETS
from seaweedfs_tpu.storage.types import (MAX_POSSIBLE_VOLUME_SIZE,
                                         bytes_to_offset, entry_size,
                                         offset_to_bytes)
from seaweedfs_tpu.storage.volume import Volume

GB = 1 << 30
BEYOND = 33 * GB  # past the 4-byte-offset ceiling


def test_offset_codec_widths():
    assert offset_to_bytes(BEYOND, 5) == \
        (BEYOND // 8).to_bytes(5, "big")
    assert bytes_to_offset(offset_to_bytes(BEYOND, 5)) == BEYOND
    with pytest.raises(ValueError, match="exceeds"):
        offset_to_bytes(MAX_POSSIBLE_VOLUME_SIZE + 8, 4)
    assert entry_size(5) == 17


def make_big_volume(tmp_path, n_needles=5):
    """Volume whose .dat sparsely extends past 32GB; needles land beyond
    the 4-byte-offset ceiling."""
    v = Volume(str(tmp_path), "", 9, create=True, offset_width=5)
    assert v.offset_width == 5
    assert v.super_block.flags & FLAG_5_BYTE_OFFSETS
    # leap the append cursor past 32GB (sparse: no data written)
    v.dat.truncate(BEYOND)
    rng = np.random.default_rng(8)
    payloads = {}
    for i in range(1, n_needles + 1):
        data = rng.integers(0, 256, 3000 + i).astype(np.uint8).tobytes()
        v.write_needle(Needle(id=i, cookie=0xC, data=data))
        payloads[i] = data
    return v, payloads


def test_big_volume_write_read_cold_boot(tmp_path):
    v, payloads = make_big_volume(tmp_path)
    nv = v.nm.get(1)
    assert nv.offset >= BEYOND  # really past the 32GB line
    for i, data in payloads.items():
        assert v.read_needle(Needle(id=i, cookie=0xC)).data == data
    v.delete_needle(Needle(id=2, cookie=0xC))
    v.close()
    # 17-byte .idx records round-trip through a cold boot
    assert os.path.getsize(str(tmp_path / "9.idx")) % 17 == 0
    v2 = Volume(str(tmp_path), "", 9)
    assert v2.offset_width == 5
    for i, data in payloads.items():
        if i == 2:
            with pytest.raises(Exception):
                v2.read_needle(Needle(id=2, cookie=0xC))
        else:
            assert v2.read_needle(Needle(id=i, cookie=0xC)).data == data
    v2.close()


def test_big_volume_ecx_and_locate(tmp_path):
    """.ecx with 17B records: sorted write, binary search, journal
    tombstone replay, and .idx regeneration."""
    v, payloads = make_big_volume(tmp_path)
    v.close()
    base = str(tmp_path / "9")
    write_sorted_file_from_idx(base)
    assert os.path.getsize(base + ".ecx") % 17 == 0
    # fabricate .ec00 so superblock introspection works (sparse copy of
    # the .dat head suffices — only the first 8 bytes are read)
    with open(base + ".dat", "rb") as f, open(base + ".ec00", "wb") as out:
        out.write(f.read(4096))
    assert read_ec_volume_superblock(base).offset_width == 5
    ev = EcVolume(str(tmp_path), "", 9)
    assert ev.offset_width == 5
    offset, size, intervals = ev.locate_needle(3)
    # size is the stored needle-body size (payload + meta), >= payload
    assert offset >= BEYOND and size >= len(payloads[3]) and intervals
    # delete -> journal -> rebuild replay keeps 17B framing
    assert ev.delete_needle(3)
    with pytest.raises(KeyError):
        ev.locate_needle(3)
    ev.close()
    from seaweedfs_tpu.ec.ec_volume import rebuild_ecx_file
    rebuild_ecx_file(base, 5)
    ev2 = EcVolume(str(tmp_path), "", 9)
    with pytest.raises(KeyError):
        ev2.locate_needle(3)
    assert ev2.locate_needle(4)[0] >= BEYOND
    ev2.close()
    # .ecx + .ecj -> .idx keeps width
    write_idx_file_from_ec_index(base)
    entries = dict((nid, (off, sz)) for nid, off, sz in
                   walk_index_file(base + ".idx", 5))
    assert entries[4][0] >= BEYOND


def test_big_volume_compaction_keeps_width(tmp_path):
    v, payloads = make_big_volume(tmp_path, n_needles=4)
    v.delete_needle(Needle(id=1, cookie=0xC))
    v.compact()
    v.commit_compact()
    assert v.offset_width == 5  # flags survive the superblock rewrite
    for i in (2, 3, 4):
        assert v.read_needle(Needle(id=i, cookie=0xC)).data == payloads[i]
    v.close()


@pytest.mark.skipif(not os.environ.get("SW_BIG_TESTS"),
                    reason="writes ~46GB of shards; set SW_BIG_TESTS=1")
def test_full_ec_encode_of_33gb_volume(tmp_path):
    """The 'done' bar: encode+rebuild of a >32GB .dat. Gated —
    shard output is ~46GB of real disk writes."""
    from seaweedfs_tpu.ec import rebuild_ec_files, to_ext, write_ec_files
    from seaweedfs_tpu.ops.codec import get_codec
    from seaweedfs_tpu.util import file_sha256
    v, payloads = make_big_volume(tmp_path)
    v.close()
    base = str(tmp_path / "9")
    codec = get_codec(10, 4, backend="native")
    write_ec_files(base, codec=codec, slab=8 << 20, pipelined=False)
    digests = []
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            digests.append(file_sha256(f))
    for sid in (0, 5, 11, 13):
        os.remove(base + to_ext(sid))
    rebuilt = rebuild_ec_files(base, codec=codec, pipelined=False)
    assert sorted(rebuilt) == [0, 5, 11, 13]
    for i in (0, 5, 11, 13):
        with open(base + to_ext(i), "rb") as f:
            assert file_sha256(f) == digests[i]
