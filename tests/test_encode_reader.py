"""The encode's .dat reader (ec/encoder._dat_slabs): every dispatch's
shape and bytes against the reader it replaced, kept here as the oracle.

The oracle is the old three-pass logic verbatim — per block a seek +
read into a fresh bytes, assigned into a zeroed (k, block) row slab,
row slabs concatenated up to the target width — so dispatch boundaries
(jit buckets, dispatch counts) and zero padding are held to what every
earlier PR measured, for every geometry and not only the cells'.
"""

import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import TOTAL_SHARDS, to_ext, write_ec_files
from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ops.codec import NumpyCodec, get_codec
from seaweedfs_tpu.util.profiling import StageTimer


@pytest.fixture(autouse=True)
def empty_slab_pool():
    """Each test starts with no recycled slab (the pool is the module's)."""
    encoder._SLAB_POOL.clear()
    yield
    encoder._SLAB_POOL.clear()


def _oracle_row_slabs(f, k, start, block_size, slab):
    step = min(slab, block_size)
    for off in range(0, block_size, step):
        width = min(step, block_size - off)
        data = np.zeros((k, width), dtype=np.uint8)
        for i in range(k):
            f.seek(start + i * block_size + off)
            chunk = f.read(width)
            if chunk:
                data[i, :len(chunk)] = np.frombuffer(chunk, dtype=np.uint8)
        yield data


def _oracle_row_slab_stream(path, dat_size, k, large_block, small_block, slab):
    with open(path, "rb") as f:
        remaining, processed = dat_size, 0
        while remaining > large_block * k:
            yield from _oracle_row_slabs(f, k, processed, large_block, slab)
            remaining -= large_block * k
            processed += large_block * k
        while remaining > 0:
            yield from _oracle_row_slabs(f, k, processed, small_block, slab)
            remaining -= small_block * k
            processed += small_block * k


def _oracle_dispatches(path, dat_size, k, large_block, small_block, slab,
                       target_width):
    batch, total = [], 0
    for data in _oracle_row_slab_stream(path, dat_size, k, large_block,
                                        small_block, slab):
        if batch and total + data.shape[1] > target_width:
            yield np.concatenate(batch, axis=1)
            batch, total = [], 0
        batch.append(data)
        total += data.shape[1]
    if batch:
        yield np.concatenate(batch, axis=1)


def _write_dat(tmp_path, nbytes, seed=5, name="1.dat"):
    rng = np.random.default_rng(seed)
    path = str(tmp_path / name)
    with open(path, "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
    return path


def _dispatches(path, dat_size, k, large_block, small_block, slab, target):
    return [d for _, d in encoder._dat_slabs(
        path, dat_size, k, large_block, small_block, slab, target,
        StageTimer())]


# (id, dat bytes, k, large block, small block, slab, target width or None
# for the slab itself). Blocks are tiny so that each case is a few KiB.
GEOMETRIES = [
    ("ends-mid-block", 10 * 64 * 3 + 5 * 64 + 17, 10, 4096, 64, 512, None),
    ("ends-mid-row-on-a-block", 10 * 64 * 3 + 5 * 64, 10, 4096, 64, 512, None),
    ("ends-on-a-row", 10 * 64 * 8, 10, 4096, 64, 512, None),
    ("one-byte-into-a-row", 10 * 64 * 8 + 1, 10, 4096, 64, 512, None),
    ("shorter-than-a-block", 23, 10, 4096, 64, 512, None),
    ("empty", 0, 10, 4096, 64, 512, None),
    ("exactly-one-large-row-is-small-rows", 10 * 1024, 10, 1024, 64, 512,
     None),
    ("large-rows-then-small-rows", 2 * 10 * 1024 + 10 * 64 * 5 + 33, 10, 1024,
     64, 256, None),
    ("three-large-rows-slab-not-dividing-them", 3 * 6 * 1000 + 777, 6, 1000,
     50, 384, None),
    ("slab-smaller-than-block", 10 * 64 * 4 + 100, 10, 4096, 64, 16, None),
    ("slab-equals-block", 10 * 64 * 4 + 100, 10, 4096, 64, 64, None),
    ("slab-larger-not-a-multiple", 10 * 64 * 9 + 100, 10, 4096, 64, 160, None),
    ("block-not-a-multiple-of-slab", 10 * 100 * 3 + 250, 10, 4000, 100, 48,
     None),
    ("k6", 6 * 64 * 7 + 91, 6, 4096, 64, 256, None),
    ("k20", 20 * 64 * 7 + 1291, 20, 4096, 64, 256, None),
    ("k20-large-rows", 20 * 512 * 2 + 20 * 32 * 3 + 7, 20, 512, 32, 128, None),
    ("target-narrower-than-slab", 10 * 64 * 9 + 100, 10, 4096, 64, 512, 448),
    ("target-narrower-than-a-piece", 2 * 10 * 1024 + 700, 10, 1024, 64, 300,
     256),
]


@pytest.mark.parametrize(
    "nbytes,k,large_block,small_block,slab,target",
    [pytest.param(*g[1:], id=g[0]) for g in GEOMETRIES])
def test_dispatches_match_the_old_reader(tmp_path, nbytes, k, large_block,
                                         small_block, slab, target):
    path = _write_dat(tmp_path, nbytes)
    target = target or slab
    want = list(_oracle_dispatches(path, nbytes, k, large_block, small_block,
                                   slab, target))
    got = _dispatches(path, nbytes, k, large_block, small_block, slab, target)
    assert [d.shape for d in got] == [d.shape for d in want]
    for n, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == np.uint8 and g.flags.c_contiguous
        assert np.array_equal(g, w), f"dispatch {n} differs"
    assert sum(d.shape[1] for d in got) == encoder.ec_shard_base_size(
        nbytes, large_block, small_block, k)


def test_unread_tail_is_zeroed_not_left_over(tmp_path, monkeypatch):
    """A slab comes with whatever its last user (or the allocator) left
    in it: with every fresh one pre-filled with 0xFF, and again recycled
    from a longer volume, padding the reader forgot would show."""
    nbytes, k, large_block, small_block, slab = 10 * 64 * 2 + 3 * 64 + 9, \
        10, 4096, 64, 512
    path = _write_dat(tmp_path, nbytes)
    want = list(_oracle_dispatches(path, nbytes, k, large_block, small_block,
                                   slab, slab))
    real_empty = np.empty

    def dirty_empty(shape, dtype=float, **kw):
        out = real_empty(shape, dtype, **kw)
        out.fill(0xFF)
        return out

    monkeypatch.setattr(encoder.np, "empty", dirty_empty)
    got = _dispatches(path, nbytes, k, large_block, small_block, slab, slab)
    monkeypatch.undo()
    assert len(got) == len(want) == 1
    assert np.array_equal(got[0], want[0])
    assert not got[0][4:, 2 * 64:].any()   # rows past the file's end

    full = _write_dat(tmp_path, 10 * 64 * 8, seed=6, name="2.dat")
    (used,) = _dispatches(full, 10 * 64 * 8, k, large_block, small_block,
                          slab, slab)          # every column filled
    encoder._give_slab(used)
    (again,) = _dispatches(path, nbytes, k, large_block, small_block, slab,
                           slab)
    assert again.base is used.base         # the recycled memory ...
    assert np.array_equal(again, want[0])  # ... reads as the old reader's


def test_take_slab_reuses_what_fits_and_drops_what_does_not():
    small = encoder._take_slab(10, 64)
    big = encoder._take_slab(10, 512)
    assert small.shape == (10, 64) and small.flags.c_contiguous
    encoder._give_slab(big)
    tail = encoder._take_slab(10, 448)      # a volume's narrower last call
    assert tail.base is big.base and tail.shape == (10, 448)
    assert tail.flags.c_contiguous and tail.flags.writeable
    encoder._give_slab(tail)
    encoder._give_slab(small)
    wide = encoder._take_slab(20, 512)      # neither holds it
    assert wide.base is not big.base and wide.base is not small.base
    assert len(encoder._SLAB_POOL) == 0     # both were let go
    for _ in range(3 * encoder._SLAB_POOL.maxlen):
        encoder._give_slab(encoder._take_slab(2, 8))
    assert len(encoder._SLAB_POOL) == 1


def test_short_read_before_the_tail_raises(tmp_path):
    """A .dat that is shorter than the size the encode was planned for
    (truncated under it) is an error, not silent zero shards."""
    path = _write_dat(tmp_path, 10 * 64 * 2)
    with pytest.raises(IOError, match="short .dat read"):
        _dispatches(path, 10 * 64 * 4, 10, 4096, 64, 512, 512)


def test_one_preadv_per_small_row_of_the_cells_geometry(tmp_path,
                                                        monkeypatch):
    """RS(10,4), 1 MiB small blocks, 8 MiB slab: the first dispatch of a
    volume is eight rows, each ONE scatter read of ten 1 MiB ranges into
    the ten slab rows — 8 calls, not 80 seek/read pairs."""
    path = str(tmp_path / "1.dat")
    nbytes = 8 * 10 << 20
    with open(path, "wb") as f:
        f.truncate(nbytes)                      # sparse: reads as zeros
        f.seek(3 * (10 << 20) + (4 << 20) + 5)  # row 3, block 4, byte 5
        f.write(b"\xa5")
    calls = []
    real_preadv = os.preadv

    def counting_preadv(fd, buffers, offset, *flags):
        calls.append((offset, [len(memoryview(b)) for b in buffers]))
        return real_preadv(fd, buffers, offset, *flags)

    monkeypatch.setattr(encoder.os, "preadv", counting_preadv)
    (first,) = _dispatches(path, nbytes, 10, 1 << 30, 1 << 20, 8 << 20,
                           8 << 20)
    monkeypatch.undo()
    assert first.shape == (10, 8 << 20)
    assert calls == [(row * (10 << 20), [1 << 20] * 10) for row in range(8)]
    assert first[4, (3 << 20) + 5] == 0xA5 and first.sum() == 0xA5


LARGE, SMALL, SLAB = 10000, 100, 512


def _read_shards(base):
    out = []
    for i in range(TOTAL_SHARDS):
        with open(base + to_ext(i), "rb") as f:
            out.append(f.read())
    return out


def _oracle_shards(path, nbytes, codec):
    """Shard bytes from the OLD reader's row slabs, each encoded alone —
    independent of the new reader and of any batching."""
    shards = [bytearray() for _ in range(TOTAL_SHARDS)]
    for data in _oracle_row_slab_stream(path, nbytes, 10, LARGE, SMALL,
                                        SLAB):
        parity = np.asarray(codec.encode(data), dtype=np.uint8)
        for i in range(10):
            shards[i] += data[i].tobytes()
        for j in range(4):
            shards[10 + j] += parity[j].tobytes()
    return [bytes(s) for s in shards]


@pytest.mark.parametrize("backend,pipelined", [
    ("numpy", False), ("native", False), ("tpu", True), ("mesh", True)])
def test_flat_shards_match_the_old_readers(tmp_path, monkeypatch, backend,
                                           pipelined):
    """write_ec_files through the new reader, on every backend, against
    shards built from the old reader's row slabs; and on the pipelined
    path no slab-sized concatenate is left."""
    nbytes = 10 * LARGE * 2 + 10 * SMALL * 37 + 61
    path = _write_dat(tmp_path, nbytes, seed=9)
    base = path[:-len(".dat")]
    want = _oracle_shards(path, nbytes, NumpyCodec(10, 4))
    joined = []
    real_concatenate = np.concatenate

    def watching_concatenate(arrays, *a, **kw):
        out = real_concatenate(arrays, *a, **kw)
        joined.append(out.nbytes)
        return out

    codec = get_codec(10, 4, backend=backend)
    monkeypatch.setattr(encoder.np, "concatenate", watching_concatenate)
    write_ec_files(base, codec=codec, large_block=LARGE, small_block=SMALL,
                   slab=SLAB, pipelined=pipelined)
    monkeypatch.undo()
    assert _read_shards(base) == want
    if pipelined:
        assert [n for n in joined if n >= 10 * SMALL] == []


class _RecordingSink:
    """The part of ec.spread.StripedSpreadSink write_ec_files calls."""

    def __init__(self):
        self.slabs_written = []
        self.columns = 0

    def write_stripe(self, data, parity, done=None):
        assert data.shape[1] == parity.shape[1]
        self.slabs_written.append(data.base)
        self.columns += data.shape[1]
        done()      # written as it came: nothing of the stripe is kept


@pytest.mark.parametrize("backend,pipelined", [
    ("numpy", False), ("tpu", True), ("mesh", True)])
def test_a_slab_goes_back_only_after_its_stripe_is_written(
        tmp_path, monkeypatch, backend, pipelined):
    nbytes = 10 * SMALL * 41 + 7
    path = _write_dat(tmp_path, nbytes, seed=13)
    sink = _RecordingSink()
    given = []

    def checked_give(data):
        assert any(data.base is s for s in sink.slabs_written), \
            "slab recycled before its stripe reached the sink"
        given.append(data.base)
        real_give(data)

    real_give = encoder._give_slab
    monkeypatch.setattr(encoder, "_give_slab", checked_give)
    write_ec_files(path[:-len(".dat")], codec=get_codec(10, 4, backend=backend),
                   large_block=LARGE, small_block=SMALL, slab=SLAB,
                   pipelined=pipelined, sink=sink)
    assert sink.columns == encoder.ec_shard_base_size(nbytes, LARGE, SMALL, 10)
    assert len(given) == -(-sink.columns // (SLAB - SLAB % SMALL))  # each call
    assert 1 <= len(encoder._SLAB_POOL) <= encoder._SLAB_POOL.maxlen


@pytest.mark.parametrize("slab,recut", [(3000, True), (2048, False)])
def test_a_piggyback_stripe_is_never_in_the_pool(tmp_path, monkeypatch,
                                                 slab, recut):
    """A stripe that is the reader's slab goes back when it is written;
    one the window re-cut made of copies lets its slab go back at once.
    Either way no stripe the sink is handed lies in the pool, and the
    shards are the ones a pool that takes nothing back gives."""
    path = _write_dat(tmp_path, 77_003, seed=11)
    base = path[:-len(".dat")]
    geometry = dict(codec=NumpyCodec(10, 4), large_block=4096,
                    small_block=512, slab=slab, pipelined=False,
                    layout="piggyback")
    monkeypatch.setattr(encoder, "_give_slab", lambda data: None)
    write_ec_files(base, **geometry)
    monkeypatch.undo()
    want = _read_shards(base)
    assert len(encoder._SLAB_POOL) == 0

    class Sink:
        stripes, copies = [], 0

        def write_stripe(self, data, parity, done=None):
            assert not any(np.may_share_memory(data, buf)
                           for buf in encoder._SLAB_POOL), \
                "a stripe being written lies in the pool"
            self.copies += data.base is None
            self.stripes.append(np.concatenate([data, parity]))
            done()

    sink = Sink()
    write_ec_files(base, sink=sink, **geometry)
    got = np.concatenate(sink.stripes, axis=1)
    assert [row.tobytes() for row in got] == want
    assert (sink.copies > 0) == recut
    assert 1 <= len(encoder._SLAB_POOL) <= encoder._SLAB_POOL.maxlen
