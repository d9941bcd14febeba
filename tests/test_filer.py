"""Filer core tests.

Mirrors reference weed/filer2/filechunks_test.go (interval overlay
tables), leveldb_store_test.go (store round-trip), and
filer_delete_entry.go behavior (recursive delete + chunk queue).
"""

import pytest

from seaweedfs_tpu.filer import (
    Attr,
    Entry,
    FileChunk,
    Filer,
    MemoryStore,
    RedisStore,
    ShardedStore,
    SqliteStore,
    compact_file_chunks,
    minus_chunks,
    non_overlapping_visible_intervals,
    total_size,
    view_from_chunks,
)
from seaweedfs_tpu.filer.filer import FilerError, NotFoundError
from seaweedfs_tpu.filer.stream import read_chunked


def c(fid, offset, size, mtime):
    return FileChunk(fid=fid, offset=offset, size=size, mtime=mtime)


class FakeRedis:
    """In-process redis-protocol server: strings + lex sorted sets —
    the command subset the RedisStore speaks, validated on the real
    wire format (RESP2 over TCP)."""

    def __init__(self):
        import socket
        import threading
        self.kv = {}
        self.zsets = {}
        self.lock = threading.Lock()
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._stop = False
        threading.Thread(target=self._serve, daemon=True).start()

    def stop(self):
        self._stop = True
        try:
            self.sock.close()
        except OSError:
            pass

    def flushall(self):
        with self.lock:
            self.kv.clear()
            self.zsets.clear()

    def _serve(self):
        import threading
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._client, args=(conn,),
                             daemon=True).start()

    def _client(self, conn):
        buf = b""

        def read_line():
            nonlocal buf
            while b"\r\n" not in buf:
                chunk = conn.recv(65536)
                if not chunk:
                    raise ConnectionError
                buf += chunk
            line, buf = buf.split(b"\r\n", 1)
            return line

        def read_exact(n):
            nonlocal buf
            while len(buf) < n + 2:
                chunk = conn.recv(65536)
                if not chunk:
                    raise ConnectionError
                buf += chunk
            out, buf = buf[:n], buf[n + 2:]
            return out

        multi = None  # per-connection MULTI queue
        try:
            while True:
                line = read_line()
                assert line[:1] == b"*", line
                args = []
                for _ in range(int(line[1:])):
                    hdr = read_line()
                    assert hdr[:1] == b"$"
                    args.append(read_exact(int(hdr[1:])))
                cmd = args[0].decode().upper()
                if cmd == "MULTI":
                    multi = []
                    conn.sendall(b"+OK\r\n")
                elif cmd == "EXEC" and multi is not None:
                    replies = [self._dispatch(a) for a in multi]
                    multi = None
                    conn.sendall(b"*%d\r\n" % len(replies)
                                 + b"".join(replies))
                elif multi is not None:
                    multi.append(args)
                    conn.sendall(b"+QUEUED\r\n")
                else:
                    conn.sendall(self._dispatch(args))
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()

    @staticmethod
    def _bulk(b):
        if b is None:
            return b"$-1\r\n"
        return b"$%d\r\n%s\r\n" % (len(b), b)

    def _dispatch(self, args):
        cmd = args[0].decode().upper()
        with self.lock:
            if cmd == "PING":
                return b"+PONG\r\n"
            if cmd in ("AUTH", "SELECT"):
                return b"+OK\r\n"
            if cmd == "FLUSHALL":
                self.kv.clear()
                self.zsets.clear()
                return b"+OK\r\n"
            if cmd == "SET":
                self.kv[args[1]] = args[2]
                return b"+OK\r\n"
            if cmd == "GET":
                return self._bulk(self.kv.get(args[1]))
            if cmd == "MGET":
                return b"*%d\r\n" % (len(args) - 1) + b"".join(
                    self._bulk(self.kv.get(k)) for k in args[1:])
            if cmd == "DEL":
                n = 0
                for k in args[1:]:
                    n += self.kv.pop(k, None) is not None
                    n += self.zsets.pop(k, None) is not None
                return b":%d\r\n" % n
            if cmd == "ZADD":
                z = self.zsets.setdefault(args[1], set())
                added = args[3] not in z
                z.add(args[3])
                return b":%d\r\n" % added
            if cmd == "ZREM":
                z = self.zsets.get(args[1], set())
                removed = args[2] in z
                z.discard(args[2])
                return b":%d\r\n" % removed
            if cmd == "SCAN":
                # one-pass cursor; glob: \escape, *, ?
                import re
                pat = args[args.index(b"MATCH") + 1].decode() \
                    if b"MATCH" in args else "*"
                out, i = [], 0
                while i < len(pat):
                    ch = pat[i]
                    if ch == "\\" and i + 1 < len(pat):
                        out.append(re.escape(pat[i + 1]))
                        i += 2
                        continue
                    out.append(".*" if ch == "*" else
                               "." if ch == "?" else re.escape(ch))
                    i += 1
                rx = re.compile("^" + "".join(out) + "$", re.S)
                keys = [k for k in
                        list(self.kv) + list(self.zsets)
                        if rx.match(k.decode("utf-8", "surrogateescape"))]
                body = b"*%d\r\n" % len(keys) + b"".join(
                    self._bulk(k) for k in keys)
                return b"*2\r\n" + self._bulk(b"0") + body
            if cmd == "ZRANGEBYLEX":
                members = sorted(self.zsets.get(args[1], set()))
                lo, hi = args[2], args[3]

                def keep(m):
                    if lo == b"-":
                        ok_lo = True
                    elif lo[:1] == b"[":
                        ok_lo = m >= lo[1:]
                    else:
                        ok_lo = m > lo[1:]
                    if hi == b"+":
                        return ok_lo
                    if hi[:1] == b"[":
                        return ok_lo and m <= hi[1:]
                    return ok_lo and m < hi[1:]

                picked = [m for m in members if keep(m)]
                if len(args) >= 7 and args[4].upper() == b"LIMIT":
                    off, cnt = int(args[5]), int(args[6])
                    picked = picked[off:off + cnt]
                return b"*%d\r\n" % len(picked) + b"".join(
                    self._bulk(m) for m in picked)
        return b"-ERR unknown command\r\n"


_fake_redis_srv = None


def fake_redis():
    global _fake_redis_srv
    if _fake_redis_srv is None:
        _fake_redis_srv = FakeRedis()
    _fake_redis_srv.flushall()
    return _fake_redis_srv


class FakeMysql:
    """In-process MySQL server: real wire protocol (handshake v10,
    mysql_native_password auth incl. verification, COM_QUERY with
    OK/ERR/resultset framing), with a dict executor that pattern-
    matches exactly the statement shapes MysqlStore emits."""

    USER, PASSWORD = "weed", "sekrit"

    def __init__(self, nbe=False):
        import socket
        import threading
        # nbe: advertise sql_mode=NO_BACKSLASH_ESCAPES in the status
        # flags; the executor then expects quote-doubled literals with
        # LITERAL backslashes (what a real server in that mode parses)
        self.nbe = nbe
        self.rows = {}  # (dirhash, name) -> (directory, meta bytes)
        self.lock = threading.Lock()
        self.auth_failures = 0
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._stop = False
        threading.Thread(target=self._serve, daemon=True).start()

    def stop(self):
        self._stop = True
        try:
            self.sock.close()
        except OSError:
            pass

    def flushall(self):
        with self.lock:
            self.rows.clear()

    def _serve(self):
        import threading
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._client, args=(conn,),
                             daemon=True).start()

    # -- framing ----------------------------------------------------------

    @staticmethod
    def _recv_packet(conn, buf):
        while len(buf) < 4:
            c = conn.recv(65536)
            if not c:
                return None, buf
            buf += c
        size = int.from_bytes(buf[:3], "little")
        while len(buf) < 4 + size:
            c = conn.recv(65536)
            if not c:
                return None, buf
            buf += c
        return buf[4:4 + size], buf[4 + size:]

    @staticmethod
    def _send(conn, seq, payload):
        conn.sendall(len(payload).to_bytes(3, "little")
                     + bytes([seq]) + payload)

    @staticmethod
    def _lenenc(n):
        if n < 0xFB:
            return bytes([n])
        if n < 1 << 16:
            return b"\xfc" + n.to_bytes(2, "little")
        if n < 1 << 24:
            return b"\xfd" + n.to_bytes(3, "little")
        return b"\xfe" + n.to_bytes(8, "little")

    @property
    def _status(self):
        return 2 | (0x200 if self.nbe else 0)

    @property
    def _OK(self):
        import struct as _s
        return b"\x00\x01\x00" + _s.pack("<H", self._status) + b"\x00\x00"

    _EOF = b"\xfe\x00\x00\x02\x00"

    def _client(self, conn):
        import os
        import struct
        from seaweedfs_tpu.filer.mysql_store import _native_password
        try:
            # like a real server's scramble: no NUL byte (the client
            # strips the part's terminator, and would strip one of ours)
            nonce = bytes(b % 255 + 1 for b in os.urandom(20))
            caps = 0x1 | 0x8 | 0x200 | 0x8000 | 0x80000
            hs = (b"\x0a" + b"5.7.0-fake\x00"
                  + struct.pack("<I", 7) + nonce[:8] + b"\x00"
                  + struct.pack("<H", caps & 0xFFFF) + b"\x21"
                  + struct.pack("<H", self._status)
                  + struct.pack("<H", caps >> 16) + bytes([21])
                  + b"\x00" * 10 + nonce[8:] + b"\x00"
                  + b"mysql_native_password\x00")
            self._send(conn, 0, hs)
            buf = b""
            resp, buf = self._recv_packet(conn, buf)
            if resp is None:
                return
            # parse handshake response: caps(4) max(4) charset(1) 23x0
            pos = 32
            end = resp.index(b"\x00", pos)
            user = resp[pos:end].decode()
            pos = end + 1
            alen = resp[pos]
            auth = resp[pos + 1:pos + 1 + alen]
            want = _native_password(self.PASSWORD, nonce)
            if user != self.USER or auth != want:
                self.auth_failures += 1
                self._send(conn, 2, b"\xff" + (1045).to_bytes(2, "little")
                           + b"#28000Access denied")
                return
            self._send(conn, 2, self._OK)
            while True:
                buf2 = b""
                pkt, buf2 = self._recv_packet(conn, buf2)
                if pkt is None or pkt[:1] != b"\x03":
                    return
                self._query(conn, pkt[1:].decode())
        except OSError:
            pass
        finally:
            conn.close()

    # -- sql executor ------------------------------------------------------

    def _unescape(self, s):
        if self.nbe:
            # NO_BACKSLASH_ESCAPES: backslash is literal, '' is a quote
            return s.replace("''", "'")
        out, i = [], 0
        while i < len(s):
            ch = s[i]
            if ch == "\\" and i + 1 < len(s):
                nxt = s[i + 1]
                out.append({"0": "\x00", "n": "\n", "r": "\r",
                            "Z": "\x1a"}.get(nxt, nxt))
                i += 2
            else:
                out.append(ch)
                i += 1
        return "".join(out)

    @property
    def _STR(self):
        return r"'((?:''|[^'])*)'" if self.nbe \
            else r"'((?:[^'\\]|\\.)*)'"

    def _query(self, conn, sql):
        import re
        S = self._STR
        if sql.startswith("CREATE TABLE"):
            self._send(conn, 1, self._OK)
            return
        m = re.match(
            r"INSERT INTO filemeta \(dirhash,name,directory,meta\) "
            rf"VALUES \((-?\d+),{S},{S},X'([0-9a-f]*)'\) "
            r"ON DUPLICATE KEY UPDATE", sql)
        if m:
            dirhash = int(m.group(1))
            name = self._unescape(m.group(2))
            d = self._unescape(m.group(3))
            with self.lock:
                self.rows[(dirhash, name)] = (d, bytes.fromhex(m.group(4)))
            self._send(conn, 1, self._OK)
            return
        m = re.match(
            rf"SELECT meta FROM filemeta WHERE dirhash=(-?\d+) "
            rf"AND name={S} AND directory={S}$", sql)
        if m:
            dirhash, name = int(m.group(1)), self._unescape(m.group(2))
            d = self._unescape(m.group(3))
            with self.lock:
                hit = self.rows.get((dirhash, name))
            rows = [(hit[1],)] if hit and hit[0] == d else []
            self._resultset(conn, 1, rows)
            return
        m = re.match(
            rf"DELETE FROM filemeta WHERE dirhash=(-?\d+) "
            rf"AND name={S} AND directory={S}$", sql)
        if m:
            dirhash, name = int(m.group(1)), self._unescape(m.group(2))
            d = self._unescape(m.group(3))
            with self.lock:
                hit = self.rows.get((dirhash, name))
                if hit and hit[0] == d:
                    del self.rows[(dirhash, name)]
            self._send(conn, 1, self._OK)
            return
        m = re.match(
            rf"DELETE FROM filemeta WHERE directory={S} "
            rf"OR directory LIKE {S}$", sql)
        if m:
            base = self._unescape(m.group(1))
            pattern = self._unescape(m.group(2))
            assert pattern.endswith("/%")
            # LIKE-level unescape: backslash protects %, _ and itself
            out, i = [], 0
            pat = pattern[:-1]  # drop the trailing wildcard
            while i < len(pat):
                if pat[i] == "\\" and i + 1 < len(pat) \
                        and pat[i + 1] in "%_\\":
                    out.append(pat[i + 1])
                    i += 2
                else:
                    out.append(pat[i])
                    i += 1
            prefix = "".join(out)
            with self.lock:
                dead = [k for k, (d, _) in self.rows.items()
                        if d == base or d.startswith(prefix)]
                for k in dead:
                    del self.rows[k]
            self._send(conn, 1, self._OK)
            return
        m = re.match(
            rf"SELECT name, meta FROM filemeta WHERE dirhash=(-?\d+) "
            rf"AND name(>=?){S} AND directory={S} "
            r"ORDER BY name ASC LIMIT (\d+)$", sql)
        if m:
            dirhash, op = int(m.group(1)), m.group(2)
            start = self._unescape(m.group(3))
            d = self._unescape(m.group(4))
            limit = int(m.group(5))
            with self.lock:
                names = sorted(
                    n for (h, n), (dd, _) in self.rows.items()
                    if h == dirhash and dd == d
                    and (n >= start if op == ">=" else n > start))
                out = [(n.encode(), self.rows[(dirhash, n)][1])
                       for n in names[:limit]]
            self._resultset(conn, 2, out)
            return
        self._send(conn, 1, b"\xff" + (1064).to_bytes(2, "little")
                   + b"#42000fake cannot parse: " + sql.encode()[:100])

    def _resultset(self, conn, ncols, rows):
        seq = 1
        self._send(conn, seq, self._lenenc(ncols))
        seq += 1
        for _ in range(ncols):
            self._send(conn, seq, b"\x03def")  # minimal column def
            seq += 1
        self._send(conn, seq, self._EOF)
        seq += 1
        for row in rows:
            out = b"".join(self._lenenc(len(v)) + v for v in row)
            self._send(conn, seq, out)
            seq += 1
        self._send(conn, seq, self._EOF)


_fake_mysql_srv = None


def fake_mysql():
    global _fake_mysql_srv
    if _fake_mysql_srv is None:
        _fake_mysql_srv = FakeMysql()
    _fake_mysql_srv.flushall()
    return _fake_mysql_srv


class TestVisibleIntervals:
    # cases transcribed from reference filechunks_test.go:96-180
    def test_non_overlapping(self):
        vis = non_overlapping_visible_intervals(
            [c("a", 0, 100, 100), c("b", 100, 100, 200)])
        assert [(v.start, v.stop, v.fid) for v in vis] == [
            (0, 100, "a"), (100, 200, "b")]

    def test_full_overwrite(self):
        vis = non_overlapping_visible_intervals(
            [c("a", 0, 100, 100), c("b", 0, 100, 200)])
        assert [(v.start, v.stop, v.fid) for v in vis] == [(0, 100, "b")]

    def test_old_full_overwrite_loses(self):
        # newer smaller write splits the older chunk
        vis = non_overlapping_visible_intervals(
            [c("a", 0, 100, 100), c("b", 25, 50, 200)])
        assert [(v.start, v.stop, v.fid) for v in vis] == [
            (0, 25, "a"), (25, 75, "b"), (75, 100, "a")]
        # tail of "a" must read from inside the chunk
        assert vis[2].chunk_offset == 75

    def test_head_overwrite(self):
        vis = non_overlapping_visible_intervals(
            [c("a", 0, 100, 100), c("b", 0, 50, 200)])
        assert [(v.start, v.stop, v.fid) for v in vis] == [
            (0, 50, "b"), (50, 100, "a")]
        assert vis[1].chunk_offset == 50

    def test_tail_overwrite(self):
        vis = non_overlapping_visible_intervals(
            [c("a", 0, 100, 100), c("b", 50, 100, 200)])
        assert [(v.start, v.stop, v.fid) for v in vis] == [
            (0, 50, "a"), (50, 150, "b")]

    def test_mtime_not_order_decides(self):
        # older mtime listed later still loses
        vis = non_overlapping_visible_intervals(
            [c("b", 0, 100, 200), c("a", 0, 100, 100)])
        assert [v.fid for v in vis] == ["b"]

    def test_three_layers(self):
        vis = non_overlapping_visible_intervals(
            [c("a", 0, 300, 100), c("b", 100, 100, 200),
             c("x", 150, 25, 300)])
        assert [(v.start, v.stop, v.fid) for v in vis] == [
            (0, 100, "a"), (100, 150, "b"), (150, 175, "x"),
            (175, 200, "b"), (200, 300, "a")]


class TestChunkViews:
    def test_view_middle(self):
        views = view_from_chunks(
            [c("a", 0, 100, 100), c("b", 100, 100, 200)], 50, 100)
        assert [(v.fid, v.offset, v.size, v.logical_offset)
                for v in views] == [("a", 50, 50, 50), ("b", 0, 50, 100)]

    def test_view_whole(self):
        views = view_from_chunks([c("a", 0, 100, 100)], 0, -1)
        assert views[0].is_full_chunk

    def test_view_of_clipped_tail(self):
        views = view_from_chunks(
            [c("a", 0, 100, 100), c("b", 0, 50, 200)], 60, 20)
        assert views == [views[0]]
        v = views[0]
        assert (v.fid, v.offset, v.size) == ("a", 60, 20)

    def test_compact_and_minus(self):
        chunks = [c("a", 0, 100, 100), c("b", 0, 100, 200),
                  c("d", 200, 100, 250)]
        compacted, garbage = compact_file_chunks(chunks)
        assert {x.fid for x in compacted} == {"b", "d"}
        assert {x.fid for x in garbage} == {"a"}
        removed = minus_chunks(chunks, compacted)
        assert {x.fid for x in removed} == {"a"}

    def test_total_size(self):
        assert total_size([c("a", 0, 100, 1), c("b", 50, 100, 2)]) == 150


class TestReadChunked:
    def test_reassembly_with_overlay(self):
        blobs = {"a": bytes(range(100)), "b": bytes([255] * 50)}

        def fetch(fid, offset, size):
            return blobs[fid][offset:offset + size]

        chunks = [c("a", 0, 100, 100), c("b", 25, 50, 200)]
        out = read_chunked(chunks, 0, -1, fetch)
        assert out == blobs["a"][:25] + blobs["b"] + blobs["a"][75:]

    def test_sparse_gap_reads_zero(self):
        blobs = {"a": b"x" * 10}

        def fetch(fid, offset, size):
            return blobs[fid][offset:offset + size]

        out = read_chunked([c("a", 100, 10, 1)], 95, 20, fetch)
        assert out == b"\0" * 5 + b"x" * 10 + b"\0" * 5


@pytest.mark.parametrize("store_cls",
                         [MemoryStore, SqliteStore, ShardedStore,
                          RedisStore, "mysql", "postgres",
                          "cassandra", "etcd"])
class TestStores:
    def make(self, store_cls):
        if store_cls == "etcd":
            from seaweedfs_tpu.filer import EtcdStore
            srv = fake_etcd()
            s = EtcdStore()
            s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                         password=srv.PASSWORD)
            return s
        if store_cls == "mysql":
            from seaweedfs_tpu.filer import MysqlStore
            srv = fake_mysql()
            s = MysqlStore()
            s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                         password=srv.PASSWORD)
            return s
        if store_cls == "postgres":
            from seaweedfs_tpu.filer import PostgresStore
            srv = fake_postgres()
            s = PostgresStore()
            s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                         password=srv.PASSWORD)
            return s
        if store_cls == "cassandra":
            from seaweedfs_tpu.filer import CassandraStore
            srv = fake_cassandra()
            s = CassandraStore()
            s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                         password=srv.PASSWORD)
            return s
        s = store_cls()
        if store_cls is RedisStore:
            s.initialize(addr=f"127.0.0.1:{fake_redis().port}")
        else:
            s.initialize()
        return s

    def test_round_trip(self, store_cls):
        s = self.make(store_cls)
        e = Entry(full_path="/home/file.txt",
                  attr=Attr(mtime=123.0, mime="text/plain"),
                  chunks=[c("3,01ab", 0, 10, 5)],
                  extended={"user.k": b"\x01\x02"})
        s.insert_entry(e)
        got = s.find_entry("/home/file.txt")
        assert got.attr.mime == "text/plain"
        assert got.chunks[0].fid == "3,01ab"
        assert got.extended["user.k"] == b"\x01\x02"
        assert s.find_entry("/nope") is None

    def test_listing_pagination(self, store_cls):
        s = self.make(store_cls)
        for name in ["a", "b", "c", "d"]:
            s.insert_entry(Entry(full_path=f"/dir/{name}"))
        page = s.list_directory_entries("/dir", "", False, 2)
        assert [e.name for e in page] == ["a", "b"]
        page = s.list_directory_entries("/dir", "b", False, 10)
        assert [e.name for e in page] == ["c", "d"]
        page = s.list_directory_entries("/dir", "b", True, 10)
        assert [e.name for e in page] == ["b", "c", "d"]

    def test_delete_folder_children(self, store_cls):
        s = self.make(store_cls)
        for p in ["/x/a", "/x/sub/b", "/y/c"]:
            s.insert_entry(Entry(full_path=p))
        s.delete_folder_children("/x")
        assert s.find_entry("/x/a") is None
        assert s.find_entry("/x/sub/b") is None
        assert s.find_entry("/y/c") is not None

    def test_delete_folder_children_wildcard_paths(self, store_cls):
        # "_" and "%" in path names must not act as LIKE wildcards
        s = self.make(store_cls)
        s.insert_entry(Entry(full_path="/a_b/keepme-not"))
        s.insert_entry(Entry(full_path="/axb/keep"))
        s.delete_folder_children("/a_b")
        assert s.find_entry("/a_b/keepme-not") is None
        assert s.find_entry("/axb/keep") is not None


class TestFiler:
    def make(self):
        store = MemoryStore()
        store.initialize()
        return Filer(store)

    def test_create_makes_parents(self):
        f = self.make()
        f.create_entry(Entry(full_path="/a/b/c/file.txt"))
        assert f.find_entry("/a/b/c").is_directory
        assert f.find_entry("/a").is_directory
        assert not f.find_entry("/a/b/c/file.txt").is_directory

    def test_overwrite_queues_old_chunks(self):
        f = self.make()
        f.create_entry(Entry(full_path="/f", chunks=[c("1,aa", 0, 10, 1)]))
        f.create_entry(Entry(full_path="/f", chunks=[c("2,bb", 0, 10, 2)]))
        assert f.drain_deletion_queue() == ["1,aa"]

    def test_delete_recursive(self):
        f = self.make()
        f.create_entry(Entry(full_path="/d/x", chunks=[c("1,aa", 0, 5, 1)]))
        f.create_entry(Entry(full_path="/d/sub/y",
                             chunks=[c("2,bb", 0, 5, 1)]))
        with pytest.raises(FilerError):
            f.delete_entry("/d")
        f.delete_entry("/d", recursive=True)
        assert not f.exists("/d")
        assert set(f.drain_deletion_queue()) == {"1,aa", "2,bb"}

    def test_rename_tree(self):
        f = self.make()
        f.create_entry(Entry(full_path="/old/a/f1"))
        f.create_entry(Entry(full_path="/old/f2"))
        f.rename_entry("/old", "/new")
        assert f.exists("/new/a/f1")
        assert f.exists("/new/f2")
        assert not f.exists("/old")

    def test_rename_file(self):
        f = self.make()
        f.create_entry(Entry(full_path="/f1", chunks=[c("1,aa", 0, 5, 1)]))
        f.rename_entry("/f1", "/sub/f2")
        assert f.find_entry("/sub/f2").chunks[0].fid == "1,aa"
        assert not f.exists("/f1")

    def test_rename_into_own_subtree_rejected(self):
        f = self.make()
        f.create_entry(Entry(full_path="/a/b/file"))
        with pytest.raises(FilerError):
            f.rename_entry("/a", "/a/b/c")
        # no-op rename keeps the entry intact
        f.rename_entry("/a", "/a")
        assert f.exists("/a/b/file")

    def test_rename_over_existing_file_reclaims_chunks(self):
        f = self.make()
        f.create_entry(Entry(full_path="/src", chunks=[c("1,aa", 0, 5, 1)]))
        f.create_entry(Entry(full_path="/dst", chunks=[c("2,bb", 0, 5, 1)]))
        f.rename_entry("/src", "/dst")
        assert f.find_entry("/dst").chunks[0].fid == "1,aa"
        assert "2,bb" in f.drain_deletion_queue()

    def test_rename_onto_directory_rejected(self):
        f = self.make()
        f.create_entry(Entry(full_path="/afile"))
        f.create_entry(Entry(full_path="/adir/child"))
        with pytest.raises(FilerError):
            f.rename_entry("/afile", "/adir")

    def test_buckets(self):
        f = self.make()
        f.create_bucket("pics", replication="001")
        assert [b.name for b in f.list_buckets()] == ["pics"]
        assert f.find_entry("/buckets/pics").attr.collection == "pics"
        f.delete_bucket("pics")
        assert f.list_buckets() == []

    def test_notify_events(self):
        f = self.make()
        events = []
        f.on_update(lambda old, new, dc: events.append(
            (old.full_path if old else None,
             new.full_path if new else None)))
        f.create_entry(Entry(full_path="/n/file"))
        f.delete_entry("/n/file")
        assert (None, "/n") in events          # implicit mkdir
        assert (None, "/n/file") in events     # create
        assert ("/n/file", None) in events     # delete

    def test_not_found(self):
        f = self.make()
        with pytest.raises(NotFoundError):
            f.find_entry("/missing")


class TestMysqlStore:
    """Direct MysqlStore coverage beyond the fuzz matrix: the auth
    handshake (verified scramble), hostile path characters through the
    literal escaping, and paging."""

    def _store(self):
        from seaweedfs_tpu.filer import MysqlStore
        srv = fake_mysql()
        s = MysqlStore()
        s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                     password=srv.PASSWORD)
        return srv, s

    def test_wrong_password_access_denied(self):
        from seaweedfs_tpu.filer import MysqlStore
        from seaweedfs_tpu.filer.mysql_store import MysqlError
        srv = fake_mysql()
        s = MysqlStore()
        with pytest.raises(MysqlError, match="Access denied"):
            s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                         password="wrong")
        assert srv.auth_failures >= 1

    def test_hostile_names_roundtrip(self):
        srv, s = self._store()
        nasty = ["it's", 'qu"ote', "back\\slash", "per%cent",
                 "under_score", "new\nline"]
        for i, name in enumerate(nasty):
            e = Entry(full_path=f"/evil/{name}")
            e.attr.mime = f"m{i}"
            s.insert_entry(e)
        got = s.list_directory_entries("/evil", "", True, 100)
        assert sorted(x.name for x in got) == sorted(nasty)
        for i, name in enumerate(nasty):
            assert s.find_entry(f"/evil/{name}").attr.mime == f"m{i}"
        s.delete_folder_children("/evil")
        assert s.list_directory_entries("/evil", "", True, 100) == []
        s.close()

    def test_listing_pagination(self):
        srv, s = self._store()
        for i in range(10):
            s.insert_entry(Entry(full_path=f"/pg/f{i:02d}"))
        page1 = s.list_directory_entries("/pg", "", True, 4)
        assert [e.name for e in page1] == ["f00", "f01", "f02", "f03"]
        page2 = s.list_directory_entries("/pg", page1[-1].name, False, 4)
        assert [e.name for e in page2] == ["f04", "f05", "f06", "f07"]
        s.close()

    def test_dirhash_matches_reference_shape(self):
        """hash_string_to_long mirrors util.HashStringToLong (first 8
        md5 bytes, big-endian, signed): pin a value so the on-table
        layout stays stable."""
        from seaweedfs_tpu.filer.mysql_store import hash_string_to_long
        import hashlib
        v = hash_string_to_long("/a/b")
        b = hashlib.md5(b"/a/b").digest()[:8]
        want = int.from_bytes(b, "big", signed=True)
        assert v == want

    def test_backslash_directory_delete_is_scoped(self):
        """LIKE metacharacters in directory names must not widen the
        recursive delete: '/a\\b' must not take '/ab' with it."""
        srv, s = self._store()
        s.insert_entry(Entry(full_path="/a\\b/inner"))
        s.insert_entry(Entry(full_path="/ab/keep"))
        s.insert_entry(Entry(full_path="/a%b/keep2"))
        s.delete_folder_children("/a\\b")
        assert s.find_entry("/a\\b/inner") is None
        assert s.find_entry("/ab/keep") is not None
        assert s.find_entry("/a%b/keep2") is not None
        s.delete_folder_children("/a%b")
        assert s.find_entry("/a%b/keep2") is None
        assert s.find_entry("/ab/keep") is not None
        s.close()

    def test_no_backslash_escapes_mode(self):
        """A server running sql_mode=NO_BACKSLASH_ESCAPES treats
        backslash as a literal: the client must switch to
        quote-doubling (tracked via the status flags) or hostile names
        become injection/breakage (go-sql-driver handles the same
        flag)."""
        from seaweedfs_tpu.filer import MysqlStore
        srv = FakeMysql(nbe=True)
        try:
            s = MysqlStore()
            s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                         password=srv.PASSWORD)
            nasty = ["it's", "x',0x00),(0,'y", "back\\slash",
                     'qu"ote', "tri'''ple"]
            for i, name in enumerate(nasty):
                e = Entry(full_path=f"/nbe/{name}")
                e.attr.mime = f"m{i}"
                s.insert_entry(e)
            # exactly the inserted rows exist — the crafted name did
            # NOT inject extra rows
            assert len(srv.rows) == len(nasty)
            for i, name in enumerate(nasty):
                assert s.find_entry(f"/nbe/{name}").attr.mime == f"m{i}"
            got = s.list_directory_entries("/nbe", "", True, 100)
            assert sorted(x.name for x in got) == sorted(nasty)
            s.delete_folder_children("/nbe")
            assert len(srv.rows) == 0
            s.close()
        finally:
            srv.stop()


class FakePostgres:
    """In-process PostgreSQL server: real wire protocol (startup,
    SCRAM-SHA-256 SASL with actual proof verification, Simple Query
    framing) with a dict executor matching the statement shapes
    PostgresStore emits."""

    USER, PASSWORD = "weed", "pg-sekrit"

    def __init__(self):
        import socket
        import threading
        self.rows = {}  # (dirhash, name) -> (directory, meta)
        self.lock = threading.Lock()
        self.auth_failures = 0
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._stop = False
        threading.Thread(target=self._serve, daemon=True).start()

    def stop(self):
        self._stop = True
        try:
            self.sock.close()
        except OSError:
            pass

    def flushall(self):
        with self.lock:
            self.rows.clear()

    def _serve(self):
        import threading
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._client, args=(conn,),
                             daemon=True).start()

    # -- framing ----------------------------------------------------------

    @staticmethod
    def _recv_exact(conn, buf, n):
        while len(buf) < n:
            c = conn.recv(65536)
            if not c:
                return None, buf
            buf += c
        return buf[:n], buf[n:]

    @staticmethod
    def _msg(kind, payload):
        import struct
        return kind + struct.pack(">I", len(payload) + 4) + payload

    def _client(self, conn):
        import base64
        import hashlib
        import hmac as hmac_mod
        import os
        import struct
        try:
            buf = b""
            head, buf = self._recv_exact(conn, buf, 4)
            if head is None:
                return
            (length,) = struct.unpack(">I", head)
            startup, buf = self._recv_exact(conn, buf, length - 4)
            if startup is None:
                return
            # demand SCRAM
            snonce_salt = os.urandom(16)
            conn.sendall(self._msg(
                b"R", struct.pack(">I", 10) + b"SCRAM-SHA-256\x00\x00"))

            def read_msg(buf):
                head, buf = self._recv_exact(conn, buf, 5)
                if head is None:
                    return None, None, buf
                (ln,) = struct.unpack(">I", head[1:5])
                payload, buf = self._recv_exact(conn, buf, ln - 4)
                return head[:1], payload, buf

            kind, payload, buf = read_msg(buf)
            if kind != b"p":
                return
            # SASLInitialResponse: mech\0 + len + client-first
            mech_end = payload.index(b"\x00")
            (clen,) = struct.unpack(
                ">I", payload[mech_end + 1:mech_end + 5])
            client_first = payload[mech_end + 5:mech_end + 5 + clen]
            first_bare = client_first.split(b",,", 1)[1]
            cnonce = dict(kv.split(b"=", 1) for kv in
                          first_bare.split(b","))[b"r"].decode()
            full_nonce = cnonce + base64.b64encode(
                os.urandom(9)).decode()
            iters = 4096
            server_first = (f"r={full_nonce},"
                            f"s={base64.b64encode(snonce_salt).decode()},"
                            f"i={iters}").encode()
            conn.sendall(self._msg(
                b"R", struct.pack(">I", 11) + server_first))
            kind, payload, buf = read_msg(buf)
            if kind != b"p":
                return
            final_fields = dict(kv.split(b"=", 1) for kv in
                                payload.split(b","))
            proof = base64.b64decode(final_fields[b"p"])
            final_no_proof = payload[:payload.rindex(b",p=")]
            auth_msg = first_bare + b"," + server_first + b"," + \
                final_no_proof
            salted = hashlib.pbkdf2_hmac(
                "sha256", self.PASSWORD.encode(), snonce_salt, iters)
            client_key = hmac_mod.new(salted, b"Client Key",
                                      hashlib.sha256).digest()
            stored = hashlib.sha256(client_key).digest()
            sig = hmac_mod.new(stored, auth_msg,
                               hashlib.sha256).digest()
            recovered = bytes(a ^ b for a, b in zip(proof, sig))
            if hashlib.sha256(recovered).digest() != stored or \
                    final_fields[b"r"].decode() != full_nonce:
                self.auth_failures += 1
                conn.sendall(self._msg(
                    b"E", b"SFATAL\x00C28P01\x00"
                          b"Mpassword authentication failed\x00\x00"))
                return
            server_key = hmac_mod.new(salted, b"Server Key",
                                      hashlib.sha256).digest()
            server_sig = hmac_mod.new(server_key, auth_msg,
                                      hashlib.sha256).digest()
            conn.sendall(self._msg(
                b"R", struct.pack(">I", 12) + b"v="
                + base64.b64encode(server_sig)))
            conn.sendall(self._msg(b"R", struct.pack(">I", 0)))
            conn.sendall(self._msg(
                b"S", b"server_version\x0015.0-fake\x00"))
            conn.sendall(self._msg(b"Z", b"I"))
            while True:
                kind, payload, buf = read_msg(buf)
                if kind is None or kind == b"X":
                    return
                if kind != b"Q":
                    return
                self._query(conn, payload.rstrip(b"\x00").decode())
                conn.sendall(self._msg(b"Z", b"I"))
        except OSError:
            pass
        finally:
            conn.close()

    # -- sql executor ------------------------------------------------------

    @staticmethod
    def _unescape(s):
        return s.replace("''", "'")

    @staticmethod
    def _unlike(pat):
        out, i = [], 0
        while i < len(pat):
            if pat[i] == "\\" and i + 1 < len(pat) \
                    and pat[i + 1] in "%_\\":
                out.append(pat[i + 1])
                i += 2
            else:
                out.append(pat[i])
                i += 1
        return "".join(out)

    def _complete(self, conn, tag):
        conn.sendall(self._msg(b"C", tag + b"\x00"))

    def _resultset(self, conn, names, rows):
        import struct
        desc = [struct.pack(">H", len(names))]
        for nm in names:
            desc.append(nm.encode() + b"\x00"
                        + struct.pack(">IhIhih", 0, 0, 25, -1, -1, 0))
        conn.sendall(self._msg(b"T", b"".join(desc)))
        for row in rows:
            out = [struct.pack(">H", len(row))]
            for v in row:
                out.append(struct.pack(">i", len(v)) + v)
            conn.sendall(self._msg(b"D", b"".join(out)))
        self._complete(conn, b"SELECT %d" % len(rows))

    _STR = r"'((?:[^']|'')*)'"

    def _query(self, conn, sql):
        import re
        S = self._STR
        if sql.startswith("CREATE TABLE") or sql.startswith(
                "CREATE INDEX"):
            self._complete(conn, b"CREATE")
            return
        if sql.startswith("SET "):
            self._complete(conn, b"SET")
            return
        m = re.match(
            r"INSERT INTO filemeta \(dirhash,name,directory,meta\) "
            rf"VALUES \((-?\d+),{S},{S},'\\x([0-9a-f]*)'::bytea\) "
            r"ON CONFLICT", sql)
        if m:
            with self.lock:
                self.rows[(int(m.group(1)), self._unescape(m.group(2)))] \
                    = (self._unescape(m.group(3)),
                       bytes.fromhex(m.group(4)))
            self._complete(conn, b"INSERT 0 1")
            return
        m = re.match(
            rf"SELECT meta FROM filemeta WHERE dirhash=(-?\d+) "
            rf"AND name={S} AND directory={S}$", sql)
        if m:
            with self.lock:
                hit = self.rows.get((int(m.group(1)),
                                     self._unescape(m.group(2))))
            want_d = self._unescape(m.group(3))
            rows = [(b"\\x" + hit[1].hex().encode(),)] \
                if hit and hit[0] == want_d else []
            self._resultset(conn, ["meta"], rows)
            return
        m = re.match(
            rf"DELETE FROM filemeta WHERE dirhash=(-?\d+) "
            rf"AND name={S} AND directory={S}$", sql)
        if m:
            with self.lock:
                key = (int(m.group(1)), self._unescape(m.group(2)))
                hit = self.rows.get(key)
                if hit and hit[0] == self._unescape(m.group(3)):
                    del self.rows[key]
            self._complete(conn, b"DELETE 1")
            return
        m = re.match(
            rf"DELETE FROM filemeta WHERE directory={S} "
            rf"OR directory LIKE {S} ESCAPE '\\'$", sql)
        if m:
            base = self._unescape(m.group(1))
            pat = self._unescape(m.group(2))
            assert pat.endswith("/%"), pat
            prefix = self._unlike(pat[:-1])
            with self.lock:
                dead = [k for k, (d, _) in self.rows.items()
                        if d == base or d.startswith(prefix)]
                for k in dead:
                    del self.rows[k]
            self._complete(conn, b"DELETE %d" % len(dead))
            return
        m = re.match(
            rf"SELECT name, meta FROM filemeta WHERE dirhash=(-?\d+) "
            rf"AND name(>=?){S} AND directory={S} "
            r"ORDER BY name ASC LIMIT (\d+)$", sql)
        if m:
            dirhash, op = int(m.group(1)), m.group(2)
            start = self._unescape(m.group(3))
            d = self._unescape(m.group(4))
            limit = int(m.group(5))
            with self.lock:
                names = sorted(
                    n for (h, n), (dd, _) in self.rows.items()
                    if h == dirhash and dd == d
                    and (n >= start if op == ">=" else n > start))
                out = [(n.encode(),
                        b"\\x" + self.rows[(dirhash, n)][1].hex()
                        .encode()) for n in names[:limit]]
            self._resultset(conn, ["name", "meta"], out)
            return
        conn.sendall(self._msg(
            b"E", b"SERROR\x00C42601\x00Mfake cannot parse: "
                  + sql.encode()[:120] + b"\x00\x00"))


_fake_pg_srv = None


def fake_postgres():
    global _fake_pg_srv
    if _fake_pg_srv is None:
        _fake_pg_srv = FakePostgres()
    _fake_pg_srv.flushall()
    return _fake_pg_srv


class TestPostgresStore:
    """Direct PostgresStore coverage beyond the fuzz matrix: the
    SCRAM-SHA-256 handshake (proof actually verified, server
    signature checked back), hostile names through quote-doubling,
    LIKE scoping, and paging."""

    def _store(self):
        from seaweedfs_tpu.filer import PostgresStore
        srv = fake_postgres()
        s = PostgresStore()
        s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                     password=srv.PASSWORD)
        return srv, s

    def test_wrong_password_rejected_by_scram(self):
        from seaweedfs_tpu.filer import PostgresStore
        from seaweedfs_tpu.filer.postgres_store import PostgresError
        srv = fake_postgres()
        s = PostgresStore()
        with pytest.raises(PostgresError,
                           match="authentication failed"):
            s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                         password="wrong")
        assert srv.auth_failures >= 1

    def test_hostile_names_roundtrip(self):
        srv, s = self._store()
        nasty = ["it's", 'qu"ote', "back\\slash", "per%cent",
                 "under_score", "new\nline", "tri'''ple"]
        for i, name in enumerate(nasty):
            e = Entry(full_path=f"/pgevil/{name}")
            e.attr.mime = f"m{i}"
            s.insert_entry(e)
        assert len(srv.rows) == len(nasty)   # nothing injected
        got = s.list_directory_entries("/pgevil", "", True, 100)
        assert sorted(x.name for x in got) == sorted(nasty)
        for i, name in enumerate(nasty):
            assert s.find_entry(f"/pgevil/{name}").attr.mime == f"m{i}"
        s.delete_folder_children("/pgevil")
        assert s.list_directory_entries("/pgevil", "", True, 100) == []
        s.close()

    def test_backslash_directory_delete_is_scoped(self):
        srv, s = self._store()
        s.insert_entry(Entry(full_path="/p\\q/inner"))
        s.insert_entry(Entry(full_path="/pq/keep"))
        s.delete_folder_children("/p\\q")
        assert s.find_entry("/p\\q/inner") is None
        assert s.find_entry("/pq/keep") is not None
        s.close()

    def test_listing_pagination_and_update(self):
        srv, s = self._store()
        for i in range(8):
            s.insert_entry(Entry(full_path=f"/pgp/f{i:02d}"))
        page1 = s.list_directory_entries("/pgp", "", True, 3)
        assert [e.name for e in page1] == ["f00", "f01", "f02"]
        page2 = s.list_directory_entries("/pgp", page1[-1].name,
                                         False, 3)
        assert [e.name for e in page2] == ["f03", "f04", "f05"]
        e = Entry(full_path="/pgp/f00")
        e.attr.mime = "updated"
        s.update_entry(e)
        assert s.find_entry("/pgp/f00").attr.mime == "updated"
        s.delete_entry("/pgp/f00")
        assert s.find_entry("/pgp/f00") is None
        s.close()


class FakeCassandra:
    """In-process CQL v4 server: STARTUP/AUTHENTICATE (SASL PLAIN,
    credentials actually checked), QUERY framing with RESULT rows in
    the global-table-spec metadata shape, and a dict executor for the
    statement shapes CassandraStore emits."""

    USER, PASSWORD = "weed", "cql-sekrit"

    def __init__(self):
        import socket
        import threading
        self.rows = {}  # (directory, name) -> meta bytes
        self.lock = threading.Lock()
        self.auth_failures = 0
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._stop = False
        threading.Thread(target=self._serve, daemon=True).start()

    def stop(self):
        self._stop = True
        try:
            self.sock.close()
        except OSError:
            pass

    def flushall(self):
        with self.lock:
            self.rows.clear()

    def _serve(self):
        import threading
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._client, args=(conn,),
                             daemon=True).start()

    @staticmethod
    def _recv_exact(conn, buf, n):
        while len(buf) < n:
            c = conn.recv(65536)
            if not c:
                return None, buf
            buf += c
        return buf[:n], buf[n:]

    @staticmethod
    def _frame(stream, opcode, body):
        import struct
        return struct.pack(">BBhBI", 0x84, 0x00, stream, opcode,
                           len(body)) + body

    def _client(self, conn):
        import struct
        try:
            buf = b""
            authed = False
            while True:
                head, buf = self._recv_exact(conn, buf, 9)
                if head is None:
                    return
                stream = struct.unpack(">h", head[2:4])[0]
                opcode = head[4]
                (length,) = struct.unpack(">I", head[5:9])
                body, buf = self._recv_exact(conn, buf, length)
                if body is None:
                    return
                if opcode == 0x01:        # STARTUP -> demand auth
                    conn.sendall(self._frame(
                        stream, 0x03,
                        struct.pack(">H", 42) +
                        b"org.apache.cassandra.auth.PasswordAuthenticator"
                        [:42]))
                elif opcode == 0x0F:      # AUTH_RESPONSE: SASL PLAIN
                    (n,) = struct.unpack(">i", body[:4])
                    parts = body[4:4 + n].split(b"\x00")
                    if parts[-2:] == [self.USER.encode(),
                                      self.PASSWORD.encode()]:
                        authed = True
                        conn.sendall(self._frame(
                            stream, 0x10, struct.pack(">i", -1)))
                    else:
                        self.auth_failures += 1
                        conn.sendall(self._frame(
                            stream, 0x00, struct.pack(">i", 0x0100)
                            + struct.pack(">H", 14)
                            + b"bad credentials"[:14]))
                        return
                elif opcode == 0x07:      # QUERY
                    if not authed:
                        return
                    (qlen,) = struct.unpack(">I", body[:4])
                    cql = body[4:4 + qlen].decode()
                    self._query(conn, stream, cql)
                else:
                    return
        except OSError:
            pass
        finally:
            conn.close()

    # -- executor ---------------------------------------------------------

    @staticmethod
    def _unescape(s):
        return s.replace("''", "'")

    def _void(self, conn, stream):
        import struct
        conn.sendall(self._frame(stream, 0x08, struct.pack(">i", 1)))

    def _rows(self, conn, stream, names, rows):
        import struct
        # kind=rows, flags=global_tables_spec, metadata + rows
        body = [struct.pack(">i", 2), struct.pack(">ii", 1, len(names))]
        for s in ("ks", "filemeta"):
            body.append(struct.pack(">H", len(s)) + s.encode())
        for nm in names:
            body.append(struct.pack(">H", len(nm)) + nm.encode())
            body.append(struct.pack(">H", 0x000D))  # varchar
        body.append(struct.pack(">i", len(rows)))
        for row in rows:
            for v in row:
                body.append(struct.pack(">i", len(v)) + v)
        conn.sendall(self._frame(stream, 0x08, b"".join(body)))

    _STR = r"'((?:[^']|'')*)'"

    def _query(self, conn, stream, cql):
        import re
        S = self._STR
        if cql.startswith(("CREATE KEYSPACE", "USE ",
                           "CREATE TABLE")):
            self._void(conn, stream)
            return
        m = re.match(
            rf"INSERT INTO filemeta \(directory,name,meta\) VALUES "
            rf"\({S},{S},0x([0-9a-f]*)\)$", cql)
        if m:
            with self.lock:
                self.rows[(self._unescape(m.group(1)),
                           self._unescape(m.group(2)))] = \
                    bytes.fromhex(m.group(3))
            self._void(conn, stream)
            return
        m = re.match(
            rf"SELECT meta FROM filemeta WHERE directory={S} "
            rf"AND name={S}$", cql)
        if m:
            with self.lock:
                hit = self.rows.get((self._unescape(m.group(1)),
                                     self._unescape(m.group(2))))
            self._rows(conn, stream, ["meta"],
                       [(hit,)] if hit is not None else [])
            return
        m = re.match(
            rf"DELETE FROM filemeta WHERE directory={S} "
            rf"AND name={S}$", cql)
        if m:
            with self.lock:
                self.rows.pop((self._unescape(m.group(1)),
                               self._unescape(m.group(2))), None)
            self._void(conn, stream)
            return
        m = re.match(
            rf"DELETE FROM filemeta WHERE directory={S}$", cql)
        if m:
            d = self._unescape(m.group(1))
            with self.lock:
                for k in [k for k in self.rows if k[0] == d]:
                    del self.rows[k]
            self._void(conn, stream)
            return
        m = re.match(
            rf"SELECT name, meta FROM filemeta WHERE directory={S}"
            rf"(?: AND name(>=?){S})? "
            r"ORDER BY name ASC LIMIT (\d+)$", cql)
        if m:
            d = self._unescape(m.group(1))
            op, start = m.group(2), m.group(3)
            start = self._unescape(start) if start else None
            limit = int(m.group(4))
            with self.lock:
                names = sorted(
                    n for (dd, n) in self.rows
                    if dd == d and (
                        start is None or
                        (n >= start if op == ">=" else n > start)))
                out = [(n.encode(), self.rows[(d, n)])
                       for n in names[:limit]]
            self._rows(conn, stream, ["name", "meta"], out)
            return
        import struct
        conn.sendall(self._frame(
            stream, 0x00, struct.pack(">i", 0x2000)
            + struct.pack(">H", 20) + b"fake cannot parse: "[:20]))


_fake_cql_srv = None


def fake_cassandra():
    global _fake_cql_srv
    if _fake_cql_srv is None:
        _fake_cql_srv = FakeCassandra()
    _fake_cql_srv.flushall()
    return _fake_cql_srv


class TestCassandraStore:
    """Direct CassandraStore coverage beyond the fuzz matrix: SASL
    PLAIN auth (credentials actually checked), hostile names through
    quote-doubling, and the walk-based recursive delete over
    materialized directory entries."""

    def _store(self):
        from seaweedfs_tpu.filer import CassandraStore
        srv = fake_cassandra()
        s = CassandraStore()
        s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                     password=srv.PASSWORD)
        return srv, s

    def test_wrong_password_rejected(self):
        from seaweedfs_tpu.filer import CassandraStore
        from seaweedfs_tpu.filer.cassandra_store import CassandraError
        srv = fake_cassandra()
        s = CassandraStore()
        with pytest.raises((CassandraError, OSError)):
            s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                         password="wrong")
        assert srv.auth_failures >= 1

    def test_hostile_names_roundtrip(self):
        srv, s = self._store()
        nasty = ["it's", "tri'''ple", "per%cent", 'qu"ote',
                 "back\\slash"]
        for i, name in enumerate(nasty):
            e = Entry(full_path=f"/cqlevil/{name}")
            e.attr.mime = f"m{i}"
            s.insert_entry(e)
        # + the materialized '/cqlevil' directory marker, nothing else
        # (the crafted names did NOT inject rows)
        assert len(srv.rows) == len(nasty) + 1
        got = s.list_directory_entries("/cqlevil", "", True, 100)
        assert sorted(x.name for x in got) == sorted(nasty)
        for i, name in enumerate(nasty):
            assert s.find_entry(
                f"/cqlevil/{name}").attr.mime == f"m{i}"
        s.close()

    def test_recursive_delete_walks_materialized_tree(self):
        """Through the Filer (which materializes parents), a recursive
        delete must take the WHOLE subtree despite the partition-keyed
        layout."""
        srv, s = self._store()
        f = Filer(s)
        for p in ("/t/a/x.bin", "/t/a/b/y.bin", "/t/a/b/c/z.bin",
                  "/t/keep.bin", "/other/w.bin"):
            f.create_entry(Entry(full_path=p))
        f.delete_entry("/t/a", recursive=True,
                       ignore_recursive_error=False)
        assert s.find_entry("/t/a/x.bin") is None
        assert s.find_entry("/t/a/b/y.bin") is None
        assert s.find_entry("/t/a/b/c/z.bin") is None
        assert s.find_entry("/t/a") is None
        assert s.find_entry("/t/keep.bin") is not None
        assert s.find_entry("/other/w.bin") is not None
        s.close()

    def test_listing_pagination(self):
        srv, s = self._store()
        for i in range(7):
            s.insert_entry(Entry(full_path=f"/cqlp/f{i}"))
        p1 = s.list_directory_entries("/cqlp", "", True, 3)
        assert [e.name for e in p1] == ["f0", "f1", "f2"]
        p2 = s.list_directory_entries("/cqlp", p1[-1].name, False, 3)
        assert [e.name for e in p2] == ["f3", "f4", "f5"]
        s.close()


class FakeEtcd:
    """In-process etcd v3 JSON-gateway fake: /v3/auth/authenticate
    minting bearer tokens (credentials actually checked, tokens
    expirable mid-run) + /v3/kv/{put,range,deleterange} over a sorted
    key space — strict about base64 and about rejecting token-less or
    stale-token KV calls the way a real auth-enabled etcd does."""

    USER = "root"
    PASSWORD = "etcdpw"

    def __init__(self):
        import base64
        import http.server
        import json
        import threading

        fake = self

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _reply(self, obj, status=200):
                body = json.dumps(obj).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _err(self, msg, code=3, status=400):
                self._reply({"error": msg, "code": code}, status)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", "0"))
                try:
                    req = json.loads(self.rfile.read(n) or b"{}")
                except ValueError:
                    return self._err("etcdserver: bad json")

                if self.path == "/v3/auth/authenticate":
                    if (req.get("name") != fake.USER
                            or req.get("password") != fake.PASSWORD):
                        with fake.lock:
                            fake.auth_failures += 1
                        return self._err(
                            "etcdserver: authentication failed, invalid "
                            "user ID or password")
                    with fake.lock:
                        fake.auth_count += 1
                        token = f"tok-{fake.auth_count}"
                        fake.tokens.add(token)
                    return self._reply({"token": token})

                tok = self.headers.get("Authorization", "")
                with fake.lock:
                    if not tok:
                        return self._err("etcdserver: user name is empty")
                    if tok not in fake.tokens:
                        return self._err(
                            "etcdserver: invalid auth token", code=16)

                def b64key(name, required=True):
                    raw = req.get(name, "")
                    if not raw:
                        if required:
                            raise ValueError(name)
                        return b""
                    return base64.b64decode(raw, validate=True)

                try:
                    if self.path == "/v3/kv/put":
                        key = b64key("key")
                        value = b64key("value", required=False)
                        with fake.lock:
                            fake.kv[key] = value
                        return self._reply({"header": {}})
                    if self.path == "/v3/kv/txn":
                        with fake.lock:
                            ok = True
                            for c in req.get("compare", []):
                                key = base64.b64decode(
                                    c["key"], validate=True)
                                if c.get("target") == "CREATE":
                                    want_missing = str(
                                        c.get("create_revision",
                                              "0")) == "0"
                                    ok &= (key not in fake.kv) \
                                        == want_missing
                                elif c.get("target") == "VALUE":
                                    ok &= fake.kv.get(key) == \
                                        base64.b64decode(
                                            c.get("value", ""),
                                            validate=True)
                                else:
                                    return self._err(
                                        "etcdserver: unsupported "
                                        "compare target")
                            branch = req.get(
                                "success" if ok else "failure", [])
                            for op in branch:
                                put = op.get("request_put")
                                if put:
                                    fake.kv[base64.b64decode(
                                        put["key"], validate=True)] = \
                                        base64.b64decode(
                                            put.get("value", ""),
                                            validate=True)
                        return self._reply({"succeeded": ok})
                    if self.path in ("/v3/kv/range",
                                     "/v3/kv/deleterange"):
                        key = b64key("key")
                        end = b64key("range_end", required=False)
                        with fake.lock:
                            if end:
                                hit = [k for k in fake.kv
                                       if key <= k and
                                       (end == b"\x00" or k < end)]
                            else:
                                hit = [key] if key in fake.kv else []
                            hit.sort()
                            if self.path == "/v3/kv/deleterange":
                                for k in hit:
                                    del fake.kv[k]
                                return self._reply(
                                    {"deleted": str(len(hit))})
                            limit = int(req.get("limit", 0) or 0)
                            more = bool(limit and len(hit) > limit)
                            if limit:
                                hit = hit[:limit]
                            kvs = [{"key":
                                    base64.b64encode(k).decode(),
                                    "value":
                                    base64.b64encode(
                                        fake.kv[k]).decode()}
                                   for k in hit]
                        return self._reply({"kvs": kvs,
                                            "count": str(len(kvs)),
                                            "more": more})
                except ValueError:
                    return self._err("etcdserver: bad base64 key")
                self._err("etcdserver: unknown path " + self.path,
                          status=404)

        self.kv = {}
        self.tokens = set()
        self.auth_count = 0
        self.auth_failures = 0
        self.lock = threading.Lock()
        self.httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def expire_tokens(self):
        with self.lock:
            self.tokens.clear()

    def flushall(self):
        with self.lock:
            self.kv.clear()
            self.tokens.clear()
            self.auth_failures = 0


_fake_etcd_srv = None


def fake_etcd():
    global _fake_etcd_srv
    if _fake_etcd_srv is None:
        _fake_etcd_srv = FakeEtcd()
    _fake_etcd_srv.flushall()
    return _fake_etcd_srv


class TestEtcdStore:
    """Direct EtcdStore coverage beyond the fuzz matrix: bearer auth
    (checked + expirable), prefix-end arithmetic, and the
    subtree-delete contract the reference's own etcd store gets wrong
    (its prefix only covers direct children —
    reference weed/filer2/etcd/etcd_store.go DeleteFolderChildren)."""

    def _store(self):
        from seaweedfs_tpu.filer import EtcdStore
        srv = fake_etcd()
        s = EtcdStore()
        s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                     password=srv.PASSWORD)
        return srv, s

    def test_wrong_password_rejected(self):
        from seaweedfs_tpu.filer import EtcdStore
        from seaweedfs_tpu.filer.etcd_store import EtcdError
        srv = fake_etcd()
        s = EtcdStore()
        with pytest.raises(EtcdError):
            s.initialize(addr=f"127.0.0.1:{srv.port}", user=srv.USER,
                         password="wrong")
        assert srv.auth_failures >= 1

    def test_tokenless_kv_rejected(self):
        from seaweedfs_tpu.filer.etcd_store import EtcdClient, EtcdError
        srv = fake_etcd()
        c = EtcdClient("127.0.0.1", srv.port)  # never authenticates
        with pytest.raises(EtcdError, match="user name is empty"):
            c.put(b"/x\x00y", b"{}")

    def test_token_expiry_reauths(self):
        srv, s = self._store()
        s.insert_entry(Entry(full_path="/e/a.bin"))
        before = srv.auth_count
        srv.expire_tokens()
        got = s.find_entry("/e/a.bin")
        assert got is not None and got.name == "a.bin"
        assert srv.auth_count == before + 1
        s.close()

    def test_prefix_end(self):
        from seaweedfs_tpu.filer.etcd_store import prefix_end
        assert prefix_end(b"/a\x00") == b"/a\x01"
        assert prefix_end(b"a") == b"b"
        assert prefix_end(b"a\xff") == b"b"
        assert prefix_end(b"\xff\xff") == b"\x00"

    def test_subtree_delete_covers_unmaterialized_dirs(self):
        srv, s = self._store()
        # /t/a/b was never created as a directory entry — a
        # direct-children-only delete would strand /t/a/b\x00c.bin
        for p in ["/t/a/x.bin", "/t/a/b/c.bin", "/t/keep.bin",
                  "/other/w.bin"]:
            s.insert_entry(Entry(full_path=p))
        s.delete_folder_children("/t/a")
        assert s.find_entry("/t/a/x.bin") is None
        assert s.find_entry("/t/a/b/c.bin") is None
        assert s.find_entry("/t/keep.bin") is not None
        assert s.find_entry("/other/w.bin") is not None
        s.close()

    def test_hostile_names_round_trip(self):
        srv, s = self._store()
        names = ["sp ace", "per%cent", 'quo"te', "unié",
                 "tab\tname", "back\\slash"]
        for n in names:
            s.insert_entry(Entry(full_path=f"/h/{n}"))
        got = [e.name for e in
               s.list_directory_entries("/h", "", True, 100)]
        assert got == sorted(names)
        for n in names:
            assert s.find_entry(f"/h/{n}") is not None
        s.close()

    def test_start_name_prefix_extension(self):
        # keys "b", "ba": listing after "b" must include "ba"
        srv, s = self._store()
        for n in ["a", "b", "ba", "c"]:
            s.insert_entry(Entry(full_path=f"/p/{n}"))
        page = s.list_directory_entries("/p", "b", False, 10)
        assert [e.name for e in page] == ["ba", "c"]
        page = s.list_directory_entries("/p", "b", True, 2)
        assert [e.name for e in page] == ["b", "ba"]
        s.close()
