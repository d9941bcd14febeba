#!/usr/bin/env python3
"""What the host of a benchmark machine does with a cell's files (PR 45):

    chiprun --chips 1 --timeout 600 -- \\
        python3 benchmarks/tools/io_probe.py phases
    chiprun --chips 1 --timeout 600 -- \\
        python3 benchmarks/tools/io_probe.py regimes

No JAX, no server, no chip touched: a standing set of shard files in four
directories under $TMPDIR, and a "command" that deletes one directory's
files and writes them again from eight threads in 8 MiB `os.write`s, the
directories in turn, paced as a cell's commands are. One JSON line a
command (kept in chiprun_out/probe/io.jsonl too): its wall, the seconds
in `os.write` summed over the threads, the slowest write, and how late a
thread that only sleeps woke (`late`: summed seconds, wakes over 50 ms
late, the worst).

`phases`: the fanned cell's first size (112 files of 107.4 MB, a command
every 5.5 s) as it is, after 45 s of no writes, after `os.sync()`, at a
quarter of the bytes, under /dev/shm, and with an fsync a file.
`regimes`: a run's own writes (the encode, a warm-up round, an 80 s
window) at 8 x 512 MiB and at 4 x 512 MiB, 40 s of quiet and a sync
before each. PERF.md section 6 (PR 45) has what `phases` read; `regimes`
has not run yet (no chip was free for it).
"""

import json
import os
import shutil
import sys
import tempfile
import threading
import time

CHUNK = 8 << 20
SHARD = 107_400_000         # a shard of a 1 GiB RS(10,4) volume
SERVERS = 4
WRITERS = 8
T0 = time.perf_counter()
BUF = memoryview(bytearray(os.urandom(1 << 20) * 8))
os.makedirs("chiprun_out/probe", exist_ok=True)
LOG = open("chiprun_out/probe/io.jsonl", "a")


def emit(**line):
    line["t"] = round(time.perf_counter() - T0, 2)
    text = json.dumps(line)
    print(text, flush=True)
    LOG.write(text + "\n")
    LOG.flush()


class Late(threading.Thread):
    """Sleeps 20 ms at a time and keeps how late it woke: it allocates
    nothing and touches no file, so it is late only where the whole
    process (or its host) stood still."""

    def __init__(self):
        super().__init__(daemon=True)
        self.late, self.stalls, self.worst = 0.0, 0, 0.0

    def run(self):
        while True:
            t = time.perf_counter()
            time.sleep(0.02)
            late = time.perf_counter() - t - 0.02
            self.late += late
            self.stalls += late > 0.05
            self.worst = max(self.worst, late)

    def take(self) -> list:
        out = [round(self.late, 3), self.stalls, round(self.worst, 3)]
        self.late, self.stalls, self.worst = 0.0, 0, 0.0
        return out


LATE = Late()


def write_files(paths: list, size: int, fsync: bool = False) -> dict:
    """The files written by WRITERS threads, each through a `.part` and
    a rename, as a holder stages a shard."""
    lock = threading.Lock()
    todo = list(paths)
    total = {"write_s": 0.0, "max_chunk": 0.0, "fsync_s": 0.0}

    def work():
        write_s = slowest = fsync_s = 0.0
        while True:
            with lock:
                if not todo:
                    break
                path = todo.pop()
            fd = os.open(path + ".part",
                         os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
            left = size
            while left:
                n = min(left, CHUNK)
                t = time.perf_counter()
                os.write(fd, BUF[:n])
                took = time.perf_counter() - t
                write_s += took
                slowest = max(slowest, took)
                left -= n
            if fsync:
                t = time.perf_counter()
                os.fsync(fd)
                fsync_s += time.perf_counter() - t
            os.close(fd)
            os.rename(path + ".part", path)
        with lock:
            total["write_s"] += write_s
            total["fsync_s"] += fsync_s
            total["max_chunk"] = max(total["max_chunk"], slowest)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=work) for _ in range(WRITERS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total["wall"] = time.perf_counter() - t0
    return {k: round(v, 3) for k, v in total.items()}


def standing(root: str, files_a_server: int, size: int) -> list:
    """The standing set written once (the encode); one list of paths a
    server."""
    servers = []
    for s in range(SERVERS):
        d = os.path.join(root, f"v{s}")
        os.makedirs(d)
        servers.append([os.path.join(d, f"f{i:02d}")
                        for i in range(files_a_server)])
    wrote = write_files([p for paths in servers for p in paths], size)
    emit(phase=os.path.basename(root) + "-encode", late=LATE.take(), **wrote)
    return servers


def commands(name: str, servers: list, size: int, seconds: float,
             cycle: float, fsync: bool = False):
    """A server's files lost and written again, a command every `cycle`
    seconds, the servers in turn."""
    end = time.perf_counter() + seconds
    n = 0
    while time.perf_counter() < end:
        t0 = time.perf_counter()
        paths = servers[n % len(servers)]
        for p in paths:
            os.unlink(p)
        rm_s = time.perf_counter() - t0
        wrote = write_files(paths, size, fsync)
        emit(phase=name, n=n, rm_s=round(rm_s, 3), late=LATE.take(), **wrote)
        n += 1
        time.sleep(max(0.0, cycle - (time.perf_counter() - t0)))


def quiet(seconds: float):
    emit(phase="quiet", seconds=seconds)
    time.sleep(seconds)
    emit(phase="quiet_done", late=LATE.take())


def sync():
    t = time.perf_counter()
    os.sync()
    emit(phase="os.sync", seconds=round(time.perf_counter() - t, 3))


def phases(root: str):
    with open("/proc/mounts") as f:
        emit(phase="mounts", tmp=tempfile.gettempdir(), text=f.read()[:600])
    first = standing(os.path.join(root, "first"), 28, SHARD)
    commands("as-it-is", first, SHARD, 70, 5.5)
    quiet(45)
    commands("after-quiet", first, SHARD, 55, 5.5)
    sync()
    commands("after-sync", first, SHARD, 30, 5.5)
    shutil.rmtree(os.path.join(root, "first"))
    quarter = standing(os.path.join(root, "quarter"), 28, SHARD // 4)
    commands("quarter", quarter, SHARD // 4, 40, 5.5 / 4 + 1.0)
    shutil.rmtree(os.path.join(root, "quarter"))
    if os.path.isdir("/dev/shm"):   # a tmpfs: no disk behind the files
        shm = tempfile.mkdtemp(prefix="ioprobe_", dir="/dev/shm")
        try:
            commands("shm", standing(os.path.join(shm, "shm"), 28, SHARD),
                     SHARD, 45, 5.5)
        finally:
            shutil.rmtree(shm, ignore_errors=True)
    again = standing(os.path.join(root, "fsync"), 28, SHARD)
    commands("fsync-a-file", again, SHARD, 22, 5.5, fsync=True)


def regimes(root: str):
    for name, files_a_server, cycle in (("8x512", 28, 3.7),
                                        ("4x512", 14, 2.75),
                                        ("8x512-again", 28, 3.7)):
        quiet(40)
        sync()
        servers = standing(os.path.join(root, name), files_a_server,
                           SHARD // 2)
        commands(name + "-warm-up", servers, SHARD // 2, 4 * cycle - 0.5,
                 cycle)
        commands(name, servers, SHARD // 2, 80, cycle)
        shutil.rmtree(os.path.join(root, name))


if __name__ == "__main__":
    LATE.start()
    workdir = tempfile.mkdtemp(prefix="ioprobe_")
    try:
        {"phases": phases, "regimes": regimes}[sys.argv[1]](workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    emit(phase="done")
