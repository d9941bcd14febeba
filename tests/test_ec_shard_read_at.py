"""EcVolumeShard.read_at under the concurrency a rebuild gives it: several
ranges of one shard in flight on as many handler threads (the gather
window), through one shared shard object."""

import threading

import numpy as np

from seaweedfs_tpu.ec import to_ext
from seaweedfs_tpu.ec.ec_volume import EcVolumeShard

SIZE, RANGE = 16 << 20, 1 << 20


def _shard(tmp_path):
    base = str(tmp_path / "c_1")
    # every 8 bytes hold their own offset / 8: a range read elsewhere shows
    with open(base + to_ext(0), "wb") as f:
        f.write(np.arange(SIZE // 8, dtype=np.uint64).tobytes())
    return EcVolumeShard(base, 1, 0)


def test_concurrent_ranges_of_one_shard_each_get_their_own_bytes(tmp_path):
    shard = _shard(tmp_path)
    wrong = []

    def reads(seed):
        rng = np.random.default_rng(seed)
        for _ in range(150):
            off = int(rng.integers(0, (SIZE - RANGE) // 8)) * 8
            got = np.frombuffer(shard.read_at(off, RANGE), dtype=np.uint64)
            if len(got) != RANGE // 8 or got[0] != off // 8 \
                    or got[-1] != off // 8 + RANGE // 8 - 1:
                wrong.append(off)

    threads = [threading.Thread(target=reads, args=(n,)) for n in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    shard.close()
    assert wrong == []


def test_a_range_is_short_only_at_the_end_of_the_file(tmp_path):
    shard = _shard(tmp_path)
    assert len(shard.read_at(SIZE - 5, 100)) == 5
    assert shard.read_at(SIZE + 5, 100) == b""
    assert shard.read_at(0, 0) == b""
    shard.close()
