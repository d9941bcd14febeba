"""EC pipeline conformance tests.

Ports the reference's test strategy (ec_test.go): build a real volume,
encode with shrunken geometry (large=10000, small=100), byte-compare every
needle's .dat range against shard bytes addressed via locate_data, and
reconstruct every interval from random 10-of-14 subsets. Adds an
independent brute-force layout oracle the reference doesn't have.

Every test runs at the default RS(10,4) and at RS(6,3) and RS(20,4): a
volume's geometry is its own (its `.vif`), and the row arithmetic, the
sizes and the shard enumeration all follow it.
"""

import os
import random

import numpy as np
import pytest

from seaweedfs_tpu.ec import (DATA_SHARDS, PARITY_SHARDS, TOTAL_SHARDS,
                              locate_data, rebuild_ec_files, to_ext,
                              write_ec_files, write_sorted_file_from_idx)
from seaweedfs_tpu.ec.decoder import (find_dat_file_size,
                                      write_dat_file,
                                      write_idx_file_from_ec_index)
from seaweedfs_tpu.ec.ec_volume import EcVolume, rebuild_ecx_file
from seaweedfs_tpu.ec.layout import write_layout_sidecars
from seaweedfs_tpu.ec.shard_bits import ShardBits
from seaweedfs_tpu.ops.codec import NumpyCodec
from seaweedfs_tpu.storage.needle import Needle, get_actual_size
from seaweedfs_tpu.storage.needle_map import walk_index_file
from seaweedfs_tpu.storage.types import TOMBSTONE_FILE_SIZE
from seaweedfs_tpu.storage.volume import Volume

LARGE = 10000
SMALL = 100
SLAB = 50

geometries = pytest.mark.parametrize(
    "k,m", [(DATA_SHARDS, PARITY_SHARDS), (6, 3), (20, 4)],
    ids=["rs10-4", "rs6-3", "rs20-4"])


def _make_volume(tmp_path, vid=1, needles=40, seed=0):
    rng = np.random.default_rng(seed)
    v = Volume(str(tmp_path), "", vid, create=True)
    for i in range(1, needles + 1):
        size = int(rng.integers(1, 900))
        data = rng.integers(0, 256, size).astype(np.uint8).tobytes()
        v.write_needle(Needle(cookie=0x100 + i, id=i, data=data))
    v.close()
    return v.file_name()


def _encode(base, k=DATA_SHARDS, m=PARITY_SHARDS):
    """What `ec.encode -geometry k,m` leaves: the shard files, the
    sorted index and a .vif that names the geometry (the default's is
    left without, as a volume encoded before the keys existed has it)."""
    write_sorted_file_from_idx(base)
    write_ec_files(base, codec=NumpyCodec(k, m), large_block=LARGE,
                   small_block=SMALL, slab=SLAB)
    if (k, m) != (DATA_SHARDS, PARITY_SHARDS):
        write_layout_sidecars(base, "flat", version=3, offset_width=4,
                              ec_data_shards=k, ec_parity_shards=m)


def _shard_bytes(base, total=TOTAL_SHARDS):
    return [open(base + to_ext(i), "rb").read() for i in range(total)]


@geometries
def test_shard_files_sizes_equal(tmp_path, k, m):
    base = _make_volume(tmp_path)
    _encode(base, k, m)
    sizes = {os.path.getsize(base + to_ext(i)) for i in range(k + m)}
    assert len(sizes) == 1
    assert not os.path.exists(base + to_ext(k + m))
    dat_size = os.path.getsize(base + ".dat")
    assert sizes.pop() * k >= dat_size


@geometries
def test_every_needle_readable_via_locate(tmp_path, k, m):
    """The reference's core conformance check: .dat bytes == shard bytes
    addressed through the interval math, for every needle."""
    base = _make_volume(tmp_path)
    _encode(base, k, m)
    dat = open(base + ".dat", "rb").read()
    shards = _shard_bytes(base, k + m)
    for nid, offset, size in walk_index_file(base + ".idx"):
        actual = get_actual_size(size, 3)
        want = dat[offset:offset + actual]
        intervals = locate_data(LARGE, SMALL, len(dat), offset, actual,
                                data_shards=k)
        got = b""
        for iv in intervals:
            sid, soff = iv.to_shard_id_and_offset(LARGE, SMALL)
            got += shards[sid][soff:soff + iv.size]
        assert got == want, f"needle {nid}"


@geometries
def test_reconstruct_from_any_k(tmp_path, k, m):
    base = _make_volume(tmp_path, seed=2)
    _encode(base, k, m)
    shards = _shard_bytes(base, k + m)
    codec = NumpyCodec(k, m)
    rng = random.Random(7)
    for _ in range(5):
        keep = set(rng.sample(range(k + m), k))
        inp = [np.frombuffer(shards[i], dtype=np.uint8) if i in keep else None
               for i in range(k + m)]
        out = codec.reconstruct(inp)
        for i in range(k + m):
            assert np.array_equal(out[i],
                                  np.frombuffer(shards[i], dtype=np.uint8))


@geometries
def test_locate_against_bruteforce_layout(tmp_path, k, m):
    """Independent oracle: simulate the writer's layout byte-by-byte and
    check locate_data + to_shard_id_and_offset agree for random ranges."""
    rng = random.Random(3)
    # around one and two large rows of this geometry, and well inside
    for dat_size in (1, 99, 100, 999, 1000, 5000, k * LARGE - 1,
                     k * LARGE, k * LARGE + 1, 2 * k * LARGE,
                     2 * k * LARGE + k * SMALL + 7, 300007):
        # build byte -> (shard, shard_offset) from the encode loop's rules
        mapping = {}
        pos = 0
        remaining = dat_size
        large_row = LARGE * k
        small_row = SMALL * k
        row_starts = []
        while remaining > large_row:
            row_starts.append((pos, LARGE))
            remaining -= large_row
            pos += large_row
        while remaining > 0:
            row_starts.append((pos, SMALL))
            remaining -= small_row
            pos += small_row
        n_large = sum(1 for _, b in row_starts if b == LARGE)
        shard_off_base = {}
        large_seen = small_seen = 0
        for start, block in row_starts:
            for i in range(k):
                if block == LARGE:
                    base_off = large_seen * LARGE
                else:
                    base_off = n_large * LARGE + small_seen * SMALL
                for b in range(block):
                    logical = start + i * block + b
                    if logical < dat_size:
                        mapping[logical] = (i, base_off + b)
            if block == LARGE:
                large_seen += 1
            else:
                small_seen += 1
        for _ in range(30):
            off = rng.randrange(0, dat_size)
            size = rng.randrange(1, min(4096, dat_size - off) + 1)
            intervals = locate_data(LARGE, SMALL, dat_size, off, size,
                                    data_shards=k)
            assert sum(iv.size for iv in intervals) == size
            cursor = off
            for iv in intervals:
                sid, soff = iv.to_shard_id_and_offset(LARGE, SMALL)
                for b in range(iv.size):
                    assert mapping[cursor + b] == (sid, soff + b), \
                        f"dat_size={dat_size} off={off} size={size}"
                cursor += iv.size


@geometries
def test_rebuild_missing_shards(tmp_path, k, m):
    """m shards lost, data and parity, put back by a caller that brings
    no codec: the volume's own geometry is read from its .vif."""
    base = _make_volume(tmp_path, seed=4)
    _encode(base, k, m)
    originals = _shard_bytes(base, k + m)
    lost = sorted([0, k // 2] + list(range(k + m - (m - 2), k + m)))
    assert len(lost) == m
    for i in lost:
        os.remove(base + to_ext(i))
    stats = {}
    rebuilt = rebuild_ec_files(base, slab=SLAB, stats=stats)
    assert sorted(rebuilt) == lost
    assert (stats["k"], stats["m"]) == (k, m) and stats["lost"] == lost
    assert stats["operand"] == [m, k]
    now = _shard_bytes(base, k + m)
    for i in range(k + m):
        assert now[i] == originals[i], f"shard {i}"


@geometries
def test_rebuild_too_few_shards_raises(tmp_path, k, m):
    base = _make_volume(tmp_path, seed=5)
    _encode(base, k, m)
    for i in range(m + 1):
        os.remove(base + to_ext(i))
    with pytest.raises(ValueError):
        rebuild_ec_files(base, codec=NumpyCodec(k, m), slab=SLAB)


@geometries
def test_decode_back_to_volume(tmp_path, k, m):
    base = _make_volume(tmp_path, seed=6)
    _encode(base, k, m)
    original_dat = open(base + ".dat", "rb").read()
    original_idx = open(base + ".idx", "rb").read()
    os.remove(base + ".dat")
    os.remove(base + ".idx")
    dat_size = find_dat_file_size(base)
    assert dat_size == len(original_dat)
    write_dat_file(base, dat_size, large_block=LARGE, small_block=SMALL)
    assert open(base + ".dat", "rb").read() == original_dat
    write_idx_file_from_ec_index(base)
    # .idx from .ecx is sorted but carries the same live entry set:
    # the volume must reload fully from the decoded files
    v = Volume(str(tmp_path), "", 1)
    assert v.file_count() == 40
    v.close()


@geometries
def test_ec_volume_read_and_delete(tmp_path, k, m):
    base = _make_volume(tmp_path, seed=8)
    _encode(base, k, m)
    dat = open(base + ".dat", "rb").read()
    ev = EcVolume(str(tmp_path), "", 1)
    assert (ev.k, ev.m, ev.total) == (k, m, k + m)
    for i in range(k + m):
        ev.add_shard(i)
    assert ev.shard_ids() == list(range(k + m))

    # read through interval assembly (patch block sizes to test geometry)
    import seaweedfs_tpu.ec.ec_volume as evmod
    orig_l, orig_s = evmod.LARGE_BLOCK_SIZE, evmod.SMALL_BLOCK_SIZE
    evmod.LARGE_BLOCK_SIZE, evmod.SMALL_BLOCK_SIZE = LARGE, SMALL
    try:
        offset, size, intervals = ev.locate_needle(7)
        blob = ev.read_needle_blob(7)
        assert blob == dat[offset:offset + get_actual_size(size, 3)]
        n = Needle.from_bytes(blob, 3, expected_size=size)
        assert n.id == 7

        # degraded read: drop a shard, supply a reconstruct fetcher
        _, _, ivs = ev.locate_needle(8)
        needed = {iv.to_shard_id_and_offset(LARGE, SMALL)[0] for iv in ivs}
        victim = needed.pop()
        ev.delete_shard(victim)
        shards_bytes = _shard_bytes(base, k + m)
        codec = NumpyCodec(k, m)

        def reconstruct_fetch(vid, sid, off, ln):
            inp = [np.frombuffer(shards_bytes[i], dtype=np.uint8)
                   if i != sid else None for i in range(k + m)]
            out = codec.reconstruct(inp)
            return out[sid][off:off + ln].tobytes()

        blob8 = ev.read_needle_blob(8, reconstruct_fetch=reconstruct_fetch)
        off8, size8, _ = ev.locate_needle(8)
        assert blob8 == dat[off8:off8 + get_actual_size(size8, 3)]

        # delete: tombstone + journal, then replay journal
        assert ev.delete_needle(9)
        with pytest.raises(KeyError):
            ev.locate_needle(9)
        assert os.path.getsize(base + ".ecj") == 8
        assert not ev.delete_needle(9999)
        ev.close()
        rebuild_ecx_file(base)
        assert not os.path.exists(base + ".ecj")
        ev2 = EcVolume(str(tmp_path), "", 1)
        with pytest.raises(KeyError):
            ev2.locate_needle(9)
        ev2.close()
    finally:
        evmod.LARGE_BLOCK_SIZE, evmod.SMALL_BLOCK_SIZE = orig_l, orig_s


def test_shard_bits():
    b = ShardBits(0)
    b = b.add_shard_id(0).add_shard_id(13).add_shard_id(5)
    assert b.shard_ids() == [0, 5, 13]
    assert b.shard_id_count() == 3
    assert b.has_shard_id(5) and not b.has_shard_id(1)
    assert b.remove_shard_id(5).shard_ids() == [0, 13]
    other = ShardBits(0).add_shard_id(0).add_shard_id(1)
    assert b.minus(other).shard_ids() == [5, 13]
    assert b.plus(other).shard_ids() == [0, 1, 5, 13]
    full = ShardBits((1 << 14) - 1)
    assert full.minus_parity_shards().shard_ids() == list(range(10))


def test_shard_bits_hold_the_24_shards_of_rs20_4():
    b = ShardBits(0)
    for sid in range(24):
        b = b.add_shard_id(sid)
    assert b.shard_ids() == list(range(24)) and b.shard_id_count() == 24
    assert b == (1 << 24) - 1
    assert b.minus_parity_shards(20, 24).shard_ids() == list(range(20))
    assert b.remove_shard_id(23).shard_ids() == list(range(23))
    assert ShardBits(0).add_shard_id(31).shard_ids() == [31]
