"""Extended fuzz soak: drive the committed model-fuzz suites with
fresh seed ranges beyond the fixed CI lists. An evidence run; not
part of the committed suite.
"""
import os
import sys
import tempfile
import pathlib

os.environ["JAX_PLATFORMS"] = "cpu"
_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _root)
sys.path.insert(0, os.path.join(_root, "tests"))  # intra-test imports

from tests.test_volume_fuzz import (  # noqa: E402
    test_volume_random_ops_match_model)
from tests.test_filer_fuzz import (  # noqa: E402
    test_filer_random_ops_match_model, MemoryStore, SqliteStore,
    ShardedStore, RedisStore, MysqlStore, PostgresStore,
    CassandraStore, EtcdStore)
from tests.test_raft import (  # noqa: E402
    test_raft_fuzz_committed_entries_survive_partitions)

VOL_SEEDS = range(100, 140)
FILER_SEEDS = range(100, 110)
RAFT_SEEDS = range(100, 112)
STORES = [MemoryStore, SqliteStore, ShardedStore, RedisStore,
          MysqlStore, PostgresStore, CassandraStore, EtcdStore]


def main():
    fails = 0
    for seed in VOL_SEEDS:
        with tempfile.TemporaryDirectory() as d:
            try:
                test_volume_random_ops_match_model(pathlib.Path(d), seed)
            except Exception as e:  # noqa: BLE001
                fails += 1
                print(f"VOLUME FUZZ FAIL seed={seed}: {e!r}", flush=True)
    print(f"volume fuzz: {len(VOL_SEEDS)} seeds, {fails} failures",
          flush=True)

    f2 = 0
    for seed in FILER_SEEDS:
        for cls in STORES:
            try:
                test_filer_random_ops_match_model(cls, seed)
            except Exception as e:  # noqa: BLE001
                f2 += 1
                print(f"FILER FUZZ FAIL {cls.__name__} seed={seed}: "
                      f"{e!r}", flush=True)
    print(f"filer fuzz: {len(FILER_SEEDS)} seeds x {len(STORES)} "
          f"stores, {f2} failures", flush=True)

    f3 = 0
    for seed in RAFT_SEEDS:
        try:
            test_raft_fuzz_committed_entries_survive_partitions(seed)
        except Exception as e:  # noqa: BLE001
            f3 += 1
            print(f"RAFT FUZZ FAIL seed={seed}: {e!r}", flush=True)
    print(f"raft fuzz: {len(RAFT_SEEDS)} seeds, {f3} failures",
          flush=True)
    sys.exit(1 if (fails or f2 or f3) else 0)


if __name__ == "__main__":
    main()
