"""Controls: the program with one stated guarantee broken underneath it.

A run under a control has to come out `correct: false`; the comparison
that lets one pass proves nothing. They change the program where an
answer is produced (or, for an early acknowledgement, what is on disk
when its command returns), never the comparison. The benchmark's own runs never
use them (`run.py --control` is for the control's own runs and the tests).
"""


def corrupt_encode_matrix():
    """One parity coefficient of every coding matrix the program builds is
    changed: breaks "every shard bit-identical to the reference"."""
    from seaweedfs_tpu.ops import gf256
    sound = gf256.build_matrix

    def broken(data_shards, total_shards, *args, **kwargs):
        matrix = sound(data_shards, total_shards, *args, **kwargs).copy()
        matrix[data_shards, 0] ^= 1
        return matrix

    gf256.build_matrix = broken


def corrupt_rebuild_decode():
    """One coefficient of every decode plan is changed. ec.encode asks
    for none, so the encoded shards stay sound and only ec.rebuild goes
    wrong: breaks "a rebuilt shard is bit-identical to the encoded one"."""
    from seaweedfs_tpu.ops.codec import ReedSolomonCodec
    sound = ReedSolomonCodec.decode_plan

    def broken(self, present, data_only=False):
        src, missing, coeffs = sound(self, present, data_only)
        coeffs = coeffs.copy()
        coeffs[0, 0] ^= 1
        return src, missing, coeffs

    ReedSolomonCodec.decode_plan = broken


def _late_shard(op: str):
    """What a command that acknowledges before its last shard is written
    looks like from outside: when `op` returns, the shard file written
    last is not on disk yet, and lands 0.5 s later. Breaks "returns only
    when all shards are on their holders' disks"."""
    import glob
    import os
    import threading

    from lib.cluster import Cluster
    sound = Cluster.shell

    def early(self, name, *args):
        replies = sound(self, name, *args)
        if name == op:
            shards = [p for d in self.dirs for p in glob.glob(
                os.path.join(d, f"{self.collection}_*.ec[0-9]*"))]
            last = max(shards, key=os.path.getmtime)
            os.rename(last, last + ".late")
            timer = threading.Timer(0.5, os.rename, (last + ".late", last))
            timer.start()
        return replies

    Cluster.shell = early


CONTROLS = {
    "corrupt_encode_matrix": corrupt_encode_matrix,
    "corrupt_rebuild_decode": corrupt_rebuild_decode,
    "late_shard_after_encode": lambda: _late_shard("ec.encode"),
    "late_shard_after_rebuild": lambda: _late_shard("ec.rebuild"),
}
