"""Pipelined device path: byte-identity with the synchronous path.

The pipelined encode/rebuild (ops/pipeline.PipelinedMatmul threaded through
ec/encoder.py) must produce shard files byte-identical to the synchronous
numpy oracle — same conformance bar as the backend parity tests.
"""

import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import (TOTAL_SHARDS, rebuild_ec_files, to_ext,
                              write_ec_files)
from seaweedfs_tpu.ops.codec import NumpyCodec, get_codec
from seaweedfs_tpu.ops.pipeline import PipelinedMatmul
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

LARGE = 10000
SMALL = 100
SLAB = 512


def _make_volume(tmp_path, vid=1, needles=60, seed=3):
    rng = np.random.default_rng(seed)
    v = Volume(str(tmp_path), "", vid, create=True)
    for i in range(1, needles + 1):
        size = int(rng.integers(1, 1200))
        data = rng.integers(0, 256, size).astype(np.uint8).tobytes()
        v.write_needle(Needle(cookie=0x200 + i, id=i, data=data))
    v.close()
    return v.file_name()


def _read_shards(base):
    out = []
    for i in range(TOTAL_SHARDS):
        with open(base + to_ext(i), "rb") as f:
            out.append(f.read())
    return out


def test_pipelined_encode_matches_sync(tmp_path):
    base = _make_volume(tmp_path)
    write_ec_files(base, codec=NumpyCodec(10, 4), large_block=LARGE,
                   small_block=SMALL, slab=SLAB, pipelined=False)
    sync_shards = _read_shards(base)
    tpu = get_codec(10, 4, backend="tpu")
    write_ec_files(base, codec=tpu, large_block=LARGE,
                   small_block=SMALL, slab=SLAB, pipelined=True)
    piped_shards = _read_shards(base)
    assert sync_shards == piped_shards


def test_pipelined_rebuild_matches_originals(tmp_path):
    base = _make_volume(tmp_path)
    tpu = get_codec(10, 4, backend="tpu")
    write_ec_files(base, codec=tpu, large_block=LARGE,
                   small_block=SMALL, slab=SLAB, pipelined=True)
    originals = _read_shards(base)
    # drop a mix of data and parity shards
    dropped = [0, 3, 9, 12]
    for i in dropped:
        os.remove(base + to_ext(i))
    rebuilt = rebuild_ec_files(base, codec=tpu, slab=SLAB, pipelined=True)
    assert sorted(rebuilt) == dropped
    assert _read_shards(base) == originals


def test_pipelined_rebuild_with_extra_survivors(tmp_path):
    """More than k survivors: extras must be ignored (zero columns)."""
    base = _make_volume(tmp_path, needles=30)
    write_ec_files(base, codec=NumpyCodec(10, 4), large_block=LARGE,
                   small_block=SMALL, slab=SLAB, pipelined=False)
    originals = _read_shards(base)
    dropped = [5, 11]  # 12 survivors > k=10
    for i in dropped:
        os.remove(base + to_ext(i))
    tpu = get_codec(10, 4, backend="tpu")
    rebuilt = rebuild_ec_files(base, codec=tpu, slab=SLAB, pipelined=True)
    assert sorted(rebuilt) == dropped
    assert _read_shards(base) == originals


def test_pipelined_matmul_varied_widths():
    """Stream slabs of assorted widths incl. tails; order must hold."""
    rng = np.random.default_rng(11)
    coeffs = rng.integers(0, 256, (4, 10), dtype=np.uint8)
    oracle = NumpyCodec(10, 4)
    widths = [512, 100, 512, 1, 317, 512]
    slabs = [(idx, rng.integers(0, 256, (10, w), dtype=np.uint8))
             for idx, w in enumerate(widths)]
    pm = PipelinedMatmul(coeffs, get_codec(10, 4, "tpu"), max_width=512,
                         depth=2, prefetch=2)
    got = list(pm.stream(iter(slabs)))
    assert [meta for meta, _, _ in got] == list(range(len(widths)))
    for (meta, data, out), (_, orig) in zip(got, slabs):
        assert np.array_equal(data, orig)
        assert np.array_equal(out, oracle._matmul(coeffs, orig))


def test_pipelined_matmul_uncapped_widths_share_buckets():
    """max_width=None (degraded-read batches, every width its own):
    each width pads to its power-of-two bucket, so ragged widths in one
    bucket share one compiled program instead of one each — a cap taken
    from the batch's own width made every batch an exact-width compile."""
    from seaweedfs_tpu.ops.rs_tpu import width_bucket
    assert width_bucket(600, None) == width_bucket(700, None) == 1024
    assert width_bucket(1, None) == 512
    assert width_bucket(700, 700) == 700      # a caller's own slab width
    rng = np.random.default_rng(12)
    coeffs = rng.integers(0, 256, (1, 10), dtype=np.uint8)
    oracle = NumpyCodec(10, 4)
    codec = get_codec(10, 4, "tpu")
    for w in (600, 700, 1025):
        data = rng.integers(0, 256, (10, w), dtype=np.uint8)
        pm = PipelinedMatmul(coeffs, codec, max_width=None)
        assert pm._bucket(w) == width_bucket(w, None)
        (_, _, out), = pm.stream([(None, data)])
        assert np.array_equal(out, oracle._matmul(coeffs, data))


def test_pipelined_matmul_reader_error_propagates():
    coeffs = np.eye(4, 10, dtype=np.uint8)

    def bad_slabs():
        yield 0, np.zeros((10, 64), dtype=np.uint8)
        raise RuntimeError("disk exploded")

    pm = PipelinedMatmul(coeffs, get_codec(10, 4, "tpu"), max_width=512,
                         depth=2)
    with pytest.raises(RuntimeError, match="disk exploded"):
        list(pm.stream(bad_slabs()))


def test_pipelined_matmul_width_over_max_raises():
    coeffs = np.eye(4, 10, dtype=np.uint8)
    pm = PipelinedMatmul(coeffs, get_codec(10, 4, "tpu"), max_width=128)
    slabs = [(0, np.zeros((10, 256), dtype=np.uint8))]
    with pytest.raises(ValueError, match="exceeds max_width"):
        list(pm.stream(iter(slabs)))


@pytest.mark.parametrize("op", ["write_ec_files", "rebuild_ec_files"])
@pytest.mark.parametrize("backend", ["numpy", "native", "tpu", "mesh"])
def test_codec_decides_pipelining(tmp_path, monkeypatch, backend, op):
    """pipelined=None leaves the choice to the codec: a device codec's
    run goes through PipelinedMatmul.stream, a host codec's does not,
    and the shard files are the same bytes either way."""
    if backend == "native":
        from seaweedfs_tpu.ops.rs_native import native_available
        if not native_available():
            pytest.skip("native codec not built")
    base = _make_volume(tmp_path, needles=30)
    write_ec_files(base, codec=NumpyCodec(10, 4), large_block=LARGE,
                   small_block=SMALL, slab=SLAB, pipelined=False)
    originals = _read_shards(base)
    streams = []
    real = PipelinedMatmul.stream

    def spy(self, slabs):
        streams.append(self.codec.backend)
        return real(self, slabs)

    monkeypatch.setattr(PipelinedMatmul, "stream", spy)
    codec = get_codec(10, 4, backend=backend)
    if op == "write_ec_files":
        write_ec_files(base, codec=codec, large_block=LARGE,
                       small_block=SMALL, slab=SLAB)
    else:
        for i in (1, 8, 10, 13):
            os.remove(base + to_ext(i))
        assert rebuild_ec_files(base, codec=codec, slab=SLAB) == \
            [1, 8, 10, 13]
    assert _read_shards(base) == originals
    device = backend in ("tpu", "mesh")
    assert streams == ([backend] if device else [])


def test_host_codec_is_refused_with_a_sentence():
    """A host codec has no device_fn to stream through: the pipeline
    says so where it is built, not with an AttributeError mid-stream."""
    assert NumpyCodec(10, 4).pipelined is False
    with pytest.raises(TypeError, match="'numpy' codec computes on the "
                                        "host"):
        PipelinedMatmul(np.eye(4, 10, dtype=np.uint8), NumpyCodec(10, 4))
