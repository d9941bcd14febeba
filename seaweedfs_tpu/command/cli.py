"""The `weed`-style CLI (reference weed/weed.go + weed/command/).

Usage: python -m seaweedfs_tpu.command.cli <command> [flags]
Commands: master, volume, server, shell, benchmark, upload, download,
          version
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time

from ..ops.codec import BACKENDS as EC_BACKENDS

EC_BACKEND_HELP = (
    "erasure-coding codec: auto (the TPU where JAX computes on one, "
    "else native, else numpy), numpy, native, tpu (JAX's default "
    "device), mesh (every dispatch sharded over all local chips), "
    "tpu-own (one chip of the host's several: the process's volume "
    "servers take the local chips in turn, first server chip 0, second "
    "chip 1, ... modulo their count, and each reports its chip in "
    "/status and its heartbeat so that ec.rebuild keeps one volume in "
    "flight a distinct chip (ec.encode -collection keeps one in flight "
    "a source server, whatever chips the servers have); a process "
    "a server with one visible chip gets index 0)")

def _security_cfg(args):
    """security.toml/json + WEED_* env, loaded once per process and
    memoized on args (reference three-tier config, util/config.go +
    scaffold.go)."""
    if not hasattr(args, "_security_cfg_cache"):
        from ..util.config import load_config
        args._security_cfg_cache = load_config("security")
    return args._security_cfg_cache


def _apply_security_config(args):
    """Flag -> config -> env fallback for the JWT key."""
    from ..util.config import config_get
    if not getattr(args, "jwtKey", ""):
        args.jwtKey = config_get(_security_cfg(args),
                                 "jwt.signing.key", "") or ""


def _apply_tls_config(args):
    """TLS material (reference security/tls.go) applies to EVERY
    command: servers present cert/key, and pure clients (upload,
    download, shell, benchmark) still need the client context to reach
    a TLS cluster."""
    from ..util.config import config_get
    cfg = _security_cfg(args)
    cert = getattr(args, "tlsCert", "") or \
        config_get(cfg, "https.cert", "") or ""
    key = getattr(args, "tlsKey", "") or \
        config_get(cfg, "https.key", "") or ""
    ca = getattr(args, "tlsCa", "") or \
        config_get(cfg, "https.ca", "") or ""
    mutual = getattr(args, "tlsMutual", False) or \
        str(config_get(cfg, "https.mutual", "")).lower() in ("true", "1")
    if cert or ca:
        from ..server.http_util import configure_tls
        configure_tls(cert, key, ca, mutual=mutual)


def _apply_master_config(args) -> dict:
    """master.toml / WEED_MASTER_* (reference scaffold.go
    MASTER_TOML_EXAMPLE + master_server.go:187-232): config fills
    whatever the flags left at their defaults — an explicit flag
    always wins. Returns extra MasterServer kwargs that have no flag
    spelling (growth counts, the maintenance shell's filer)."""
    from ..util.config import config_get, load_config
    cfg = load_config("master")
    scripts = str(config_get(cfg, "master.maintenance.scripts", "")
                  or "")
    if scripts.strip() and not getattr(args, "maintenanceScripts", ""):
        # reference scripts are newline-separated; the flag is ';'
        args.maintenanceScripts = ";".join(
            ln.strip() for ln in scripts.splitlines() if ln.strip())
    sleep_m = config_get(cfg, "master.maintenance.sleep_minutes", None)
    if sleep_m is not None and \
            getattr(args, "maintenanceIntervalSeconds", 17 * 60) \
            == 17 * 60:
        args.maintenanceIntervalSeconds = float(sleep_m) * 60
    if str(config_get(cfg, "master.sequencer.type", "")) == "etcd" \
            and getattr(args, "sequencer", "auto") == "auto":
        args.sequencer = "etcd"
        urls = str(config_get(
            cfg, "master.sequencer.sequencer_etcd_urls", "") or "")
        if urls and getattr(args, "sequencerEtcd", "") \
                in ("", "127.0.0.1:2379"):
            from urllib.parse import urlparse
            first = urls.split(",")[0].strip()
            p = urlparse(first if "//" in first else "//" + first)
            if p.hostname:
                args.sequencerEtcd = f"{p.hostname}:{p.port or 2379}"
    growth = {}
    for copies, key in ((1, "copy_1"), (2, "copy_2"), (3, "copy_3"),
                        ("other", "copy_other")):
        val = config_get(cfg, f"master.volume_growth.{key}", None)
        if val is not None:
            growth[copies] = int(val)
    # [storage.backend.<kind>.<id>] tier destinations (flattened keys
    # back to the nested configure_backends shape; reference TOML
    # credential names mapped to the client's)
    nested = {}
    for key, val in cfg.items():
        parts = key.split(".")
        if parts[:2] == ["storage", "backend"] and len(parts) >= 5:
            # >5 parts happen via WEED_* env overrides, whose underscores
            # all became dots (aws_access_key_id -> aws.access.key.id):
            # everything past the 4th segment is one underscore-joined
            # param name
            _, _, kind, bid = parts[:4]
            param = "_".join(parts[4:])
            nested.setdefault(kind, {}).setdefault(bid, {})[param] = val
    backends = {}
    rename = {"aws_access_key_id": "access_key",
              "aws_secret_access_key": "secret_key"}
    for kind, ids in nested.items():
        for bid, params in ids.items():
            enabled = params.pop("enabled", False)
            if str(enabled).lower() not in ("true", "1"):
                continue
            backends.setdefault(kind, {})[bid] = {
                rename.get(k, k): v for k, v in params.items()}
    if backends:
        from ..storage.backend import configure_backends
        configure_backends(backends)
    filer_url = str(config_get(cfg, "master.filer.default_filer_url",
                               "") or "")
    maintenance_filer = ""
    if filer_url:
        from urllib.parse import urlparse
        p = urlparse(filer_url if "//" in filer_url
                     else "//" + filer_url)
        if p.hostname:
            maintenance_filer = f"{p.hostname}:{p.port or 8888}"
    return {"growth_counts": growth or None,
            "maintenance_filer_url": maintenance_filer}


def _build_sequencer(args):
    """-sequencer etcd -> an EtcdSequencer, else None (in-memory/raft).
    Shared by `weed master` and `weed server` so [master.sequencer]
    config is honored in both modes."""
    if getattr(args, "sequencer", "auto") != "etcd":
        return None
    # reference -master.sequencer etcd (weed/sequence/
    # etcd_sequencer.go): file keys granted by CAS blocks on an
    # external etcd shared by every master
    from ..topology.topology import EtcdSequencer
    meta_dir = getattr(args, "mdir", "")
    if not meta_dir:
        # sequencer.dat must never silently vanish (same hazard as
        # raft persistence, master.py raft_dir fallback): without
        # it a wiped etcd + restart re-mints live file ids. In
        # `weed server` mode (no -mdir flag) anchor it to this
        # cluster's own data dir — a fixed shared /tmp path would be
        # overwritten by any other cluster on the host
        data_dirs = getattr(args, "dir", "")
        if data_dirs:
            meta_dir = os.path.join(data_dirs.split(",")[0].strip(),
                                    "master-meta")
        else:
            import tempfile
            meta_dir = os.path.join(tempfile.gettempdir(),
                                    "weed-tpu-raft")
        os.makedirs(meta_dir, exist_ok=True)
    endpoint = getattr(args, "sequencerEtcd", "") or "127.0.0.1:2379"
    sequencer = EtcdSequencer(
        endpoint,
        user=getattr(args, "sequencerEtcdUser", ""),
        password=getattr(args, "sequencerEtcdPassword", ""),
        meta_dir=meta_dir)
    print(f"sequencer: etcd at {endpoint} (ceiling file in {meta_dir})")
    return sequencer


def cmd_master(args):
    _apply_security_config(args)
    master_cfg = _apply_master_config(args)
    from ..server.master import MasterServer
    sequencer = _build_sequencer(args)
    m = MasterServer(port=args.port, host=args.ip,
                     sequencer=sequencer,
                     volume_size_limit_mb=args.volumeSizeLimitMB,
                     default_replication=args.defaultReplication,
                     pulse_seconds=args.pulseSeconds,
                     jwt_signing_key=args.jwtKey,
                     peers=args.peers, raft_dir=args.mdir,
                     maintenance_scripts=args.maintenanceScripts,
                     maintenance_interval=args.maintenanceIntervalSeconds,
                     vacuum_interval=args.vacuumIntervalSeconds,
                     garbage_threshold=args.garbageThreshold,
                     whitelist=[w for w in args.whiteList.split(",")
                                if w],
                     metrics_address=args.metricsAddress,
                     metrics_interval=args.metricsInterval,
                     **master_cfg).start()
    print(f"master listening on {m.url}")
    _wait(m)


def _load_tier_config(path: str):
    if not path:
        return
    import json
    from ..storage.backend import configure_backends
    with open(path) as f:
        configure_backends(json.load(f))


def cmd_volume(args):
    _apply_security_config(args)
    from ..server.volume_server import VolumeServer
    _load_tier_config(args.tierConfig)
    dirs = args.dir.split(",")
    maxes = [int(x) for x in args.max.split(",")] if args.max else None
    if maxes and len(maxes) == 1:
        maxes = maxes * len(dirs)
    vs = VolumeServer(port=args.port, host=args.ip, directories=dirs,
                      master_url=args.mserver, data_center=args.dataCenter,
                      rack=args.rack, max_volume_counts=maxes,
                      pulse_seconds=args.pulseSeconds,
                      ec_backend=args.ec_backend,
                      jwt_signing_key=args.jwtKey,
                      index_kind=args.index,
                      fast_port=args.fastPort,
                      public_url=args.publicUrl,
                      read_redirect=args.readRedirect == "true",
                      file_size_limit_mb=args.fileSizeLimitMB,
                      compaction_mbps=args.compactionMBps,
                      whitelist=[w for w in args.whiteList.split(",")
                                 if w]).start()
    print(f"volume server listening on {vs.url}, "
          f"heartbeating to {args.mserver}")
    if vs.fast_plane is not None:
        print(f"native read plane on {vs.fast_url}")
    prof = _maybe_profiler(args)
    _wait(vs)
    if prof:
        prof.stop()
        print(f"cpu profile (collapsed stacks) -> {args.cpuprofile}")


def cmd_server(args):
    """Combined master + volume (+ filer) in one process
    (reference `weed server`)."""
    _apply_security_config(args)
    master_cfg = _apply_master_config(args)
    from ..server.master import MasterServer
    from ..server.volume_server import VolumeServer
    _load_tier_config(getattr(args, "tierConfig", ""))
    m = MasterServer(port=args.masterPort, host=args.ip,
                     default_replication=args.defaultReplication,
                     jwt_signing_key=args.jwtKey,
                     sequencer=_build_sequencer(args),
                     maintenance_scripts=getattr(
                         args, "maintenanceScripts", ""),
                     maintenance_interval=getattr(
                         args, "maintenanceIntervalSeconds", 17 * 60),
                     **master_cfg).start()
    dirs = args.dir.split(",")
    maxes = [int(args.max)] * len(dirs)
    vs = VolumeServer(port=args.port, host=args.ip, directories=dirs,
                      master_url=m.url, data_center=args.dataCenter,
                      rack=args.rack, pulse_seconds=args.pulseSeconds,
                      max_volume_counts=maxes,
                      ec_backend=args.ec_backend,
                      fast_port=args.fastPort,
                      jwt_signing_key=args.jwtKey).start()
    print(f"master on {m.url}, volume server on {vs.url}")
    if vs.fast_plane is not None:
        print(f"native read plane on {vs.fast_url}")
    stoppables = [vs]
    if args.filer or args.s3 or args.webdav:
        from ..server.filer_server import FilerServer
        f = FilerServer(port=args.filerPort, host=args.ip,
                        master_url=m.url,
                        jwt_signing_key=args.jwtKey,
                        notify_publisher=_notification_publisher()).start()
        print(f"filer on {f.url}")
        if args.s3:
            s3 = _start_s3(f, args.s3Port, args.ip, args.s3Config)
            print(f"s3 gateway on {s3.url}")
            stoppables.append(s3)
        if args.webdav:
            from ..server.webdav_server import WebDavServer
            w = WebDavServer(f.filer, m.url, port=args.webdavPort,
                             host=args.ip).start()
            print(f"webdav on {w.url}")
            stoppables.append(w)
        stoppables.append(f)
    stoppables.append(m)
    prof = _maybe_profiler(args)
    _wait(*stoppables)
    if prof:
        prof.stop()
        print(f"cpu profile (collapsed stacks) -> {args.cpuprofile}")


def _start_s3(filer_server, port: int, host: str, config_path: str):
    import json as _json
    from ..s3 import Iam, S3ApiServer
    iam = Iam()
    if config_path:
        with open(config_path) as fh:
            iam = Iam.from_config(_json.load(fh))
    return S3ApiServer(filer_server.filer, filer_server.master_url,
                       port=port, host=host, iam=iam).start()


def _notification_publisher():
    """notification.toml/json from the config search path (plus WEED_*
    env) — the reference filer's notification.LoadConfiguration: the
    first `[notification.<backend>]` section with enabled=true becomes
    the filer's metadata-event publisher."""
    from ..notification.queues import publisher_from_config
    from ..util.config import load_config
    pub = publisher_from_config(load_config("notification"))
    if pub is not None:
        print(f"notification -> {pub.name}")
    return pub


def cmd_filer(args):
    _apply_security_config(args)
    from ..server.filer_server import FilerServer
    db = args.db
    if args.store == "sharded":
        # the sharded store wants a DIRECTORY of shard dbs; don't reuse
        # the sqlite single-file default as a directory name
        if db == "./filer.db":
            db = "./filer_meta"
        store_options = {"path": db, "shards": args.storeShards}
    elif args.store == "sqlite":
        store_options = {"path": db}
    elif args.store == "redis":
        store_options = {"addr": args.redisAddr,
                         "password": args.redisPassword,
                         "db": args.redisDb}
    elif args.store == "mysql":
        store_options = {"addr": args.mysqlAddr,
                         "user": args.mysqlUser,
                         "password": args.mysqlPassword,
                         "database": args.mysqlDatabase}
    elif args.store == "postgres":
        store_options = {"addr": args.postgresAddr,
                         "user": args.postgresUser,
                         "password": args.postgresPassword,
                         "database": args.postgresDatabase}
    elif args.store == "cassandra":
        store_options = {"addr": args.cassandraAddr,
                         "user": args.cassandraUser,
                         "password": args.cassandraPassword,
                         "keyspace": args.cassandraKeyspace}
    elif args.store == "etcd":
        store_options = {"addr": args.etcdAddr,
                         "user": args.etcdUser,
                         "password": args.etcdPassword}
    else:
        store_options = {}
    f = FilerServer(port=args.port, host=args.ip, master_url=args.master,
                    store=args.store, store_options=store_options,
                    collection=args.collection,
                    replication=args.defaultReplicaPlacement,
                    chunk_size=args.maxMB << 20,
                    jwt_signing_key=args.jwtKey,
                    cipher=args.encryptVolumeData,
                    compress=args.compress,
                    notify_publisher=_notification_publisher()).start()
    print(f"filer listening on {f.url}, master {args.master}")
    if args.s3:
        s3 = _start_s3(f, args.s3Port, args.ip, args.s3Config)
        print(f"s3 gateway on {s3.url}")
    _wait(f)


def cmd_s3(args):
    """Standalone S3 gateway against a remote filer
    (reference weed/command/s3.go)."""
    import json as _json
    from ..filer.filer_client import FilerClient
    from ..s3 import Iam, S3ApiServer
    iam = Iam()
    if args.config:
        with open(args.config) as fh:
            iam = Iam.from_config(_json.load(fh))
    client = FilerClient(args.filer)
    master = args.master or _filer_master(args.filer)
    s3 = S3ApiServer(client, master, port=args.port, host=args.ip,
                     iam=iam).start()
    print(f"s3 gateway on {s3.url}, filer {args.filer}")
    _wait(s3)


def cmd_webdav(args):
    """WebDAV gateway (reference weed/command/webdav.go)."""
    from ..filer.filer_client import FilerClient
    from ..server.webdav_server import WebDavServer
    client = FilerClient(args.filer)
    master = args.master or _filer_master(args.filer)
    w = WebDavServer(client, master, port=args.port, host=args.ip,
                     collection=args.collection,
                     chunk_size=args.maxMB << 20).start()
    print(f"webdav on {w.url}, filer {args.filer}")
    _wait(w)


def _filer_master(filer_url: str) -> str:
    """Discover the master from the filer's status endpoint."""
    from ..server.http_util import get_json
    url = filer_url if filer_url.startswith("http") \
        else "http://" + filer_url
    return get_json(f"{url}/filer/status").get("master", "")


def cmd_shell(args):
    import seaweedfs_tpu.shell  # noqa: F401  (registers all commands)
    from ..shell.command_env import CommandEnv, run_command
    from ..shell.command_env import split_script
    env = CommandEnv(args.master, filer_url=args.filer)
    if args.c:
        # ';'-separated command lines (quote-aware), same convention as
        # the master's -maintenanceScripts cron; 'exit' stops the script
        for line in split_script(args.c):
            if not run_command(env, line):
                break
        return
    print("seaweedfs_tpu shell; 'help' lists commands, 'exit' quits")
    while True:
        try:
            line = input("> ")
        except (EOFError, KeyboardInterrupt):
            break
        if not run_command(env, line):
            break


def _maybe_profiler(args):
    """Start the all-thread stack sampler when -cpuprofile is set
    (reference -cpuprofile, weed/command/volume.go:71)."""
    path = getattr(args, "cpuprofile", "")
    if not path:
        return None
    from ..util.profiling import SamplingProfiler
    return SamplingProfiler(path).start()


def cmd_benchmark(args):
    from .benchmark import run_benchmark, run_native_benchmark
    prof = _maybe_profiler(args)
    try:
        if args.native:
            run_native_benchmark(args.master, file_size=args.size,
                                 concurrency=args.c,
                                 collection=args.collection,
                                 seconds=args.seconds, pool=args.pool,
                                 assign_batch=args.assignBatch)
        else:
            run_benchmark(args.master, num_files=args.n,
                          file_size=args.size,
                          concurrency=args.c, collection=args.collection,
                          assign_batch=args.assignBatch)
    finally:
        if prof:
            prof.stop()
            print(f"cpu profile (collapsed stacks) -> {args.cpuprofile}")


def cmd_upload(args):
    from ..client import operation as op
    max_bytes = args.maxMB << 20
    for path in args.files:
        with open(path, "rb") as f:
            data = f.read()
        if max_bytes and len(data) > max_bytes:
            from ..client.chunked import submit_chunked
            fid = submit_chunked(args.master, data, filename=path,
                                 collection=args.collection,
                                 replication=args.replication,
                                 ttl=args.ttl, chunk_size=max_bytes)
        else:
            fid = op.upload_data(args.master, data, filename=path,
                                 collection=args.collection,
                                 replication=args.replication,
                                 ttl=args.ttl)
        print(f"{path} -> {fid}")


def cmd_download(args):
    import os

    from ..client import operation as op
    os.makedirs(args.dir, exist_ok=True)
    for fid in args.fids:
        data, name = op.read_file_named(args.master, fid)
        # basename only: the stored name is uploader-controlled and
        # must never traverse outside -dir (or crash on subdirs)
        name = os.path.basename(name.replace("\\", "/"))
        out = os.path.join(args.dir, name or fid.replace(",", "_"))
        with open(out, "wb") as f:
            f.write(data)
        print(f"{fid} -> {out} ({len(data)} bytes)")


def cmd_backup(args):
    from .volume_tools import backup_volume
    out = backup_volume(args.server, args.volumeId, args.dir,
                        collection=args.collection)
    print(f"volume {out['volume']}: {out['mode']} sync, "
          f"{out['applied']} records, {out['size']} bytes")


def cmd_see(args):
    from . import volume_tools
    if args.file.endswith(".idx") or args.file.endswith(".ecx"):
        n = volume_tools.see_idx(args.file,
                                 offset_width=args.offsetWidth,
                                 limit=args.limit)
        print(f"{n} index records")
    else:
        n = volume_tools.see_dat(args.file, limit=args.limit)
        print(f"{n} needles")


def cmd_export(args):
    from .volume_tools import export_volume
    listed = export_volume(args.dir, args.volumeId,
                           collection=args.collection,
                           tar_path=args.o or None)
    for fid, name, size in listed:
        print(f"{fid}\t{name}\t{size}")
    print(f"exported {len(listed)} files")


def cmd_fix(args):
    from .volume_tools import fix_volume
    n = fix_volume(args.dir, args.volumeId, collection=args.collection)
    print(f"walked {n} records")


def cmd_compact(args):
    from .volume_tools import compact_volume
    out = compact_volume(args.dir, args.volumeId,
                         collection=args.collection,
                         method=args.method)
    print(f"volume {out['volume']}: {out['before']} -> "
          f"{out['after']} bytes")


def cmd_watch(args):
    from ..replication.sub import EventSubscriber, format_event
    sub = EventSubscriber(args.filer, since=args.since,
                          path_prefix=args.pathPrefix)
    try:
        for ts, event in sub.follow():
            print(format_event(ts, event), flush=True)
    except KeyboardInterrupt:
        pass


def cmd_filer_copy(args):
    """Copy local files/directories into the filer (reference
    `weed filer.copy`, weed/command/filer_copy.go): the last argument
    is the filer URL destination folder, everything before it is a
    local source; directories recurse, -include filters by glob, -c
    uploads files concurrently."""
    import fnmatch
    import mimetypes
    import os
    import posixpath as pp
    import urllib.parse
    from concurrent.futures import ThreadPoolExecutor

    from ..server.http_util import http_call

    if len(args.paths) < 2:
        raise SystemExit("usage: filer.copy <src>... http://filer/dir/")
    dest = args.paths[-1]
    sources = args.paths[:-1]
    parsed = urllib.parse.urlparse(
        dest if "://" in dest else "http://" + dest)
    filer = parsed.netloc
    # decode before joining: put() re-quotes the final path, so keeping
    # the URL encoding here would double-escape ("%20" -> "%2520")
    dest_dir = urllib.parse.unquote(parsed.path).rstrip("/") or "/"

    work = []  # (local_path, remote_path)
    for src in sources:
        if os.path.isdir(src):
            base = os.path.basename(os.path.abspath(src))
            for root, _dirs, files in os.walk(src):
                rel_root = os.path.relpath(root, src)
                for name in files:
                    if args.include and not fnmatch.fnmatch(
                            name, args.include):
                        continue
                    rel = name if rel_root == "." else \
                        os.path.join(rel_root, name)
                    work.append((os.path.join(root, name),
                                 pp.join(dest_dir, base,
                                         rel.replace(os.sep, "/"))))
        elif os.path.isfile(src):
            if args.include and not fnmatch.fnmatch(
                    os.path.basename(src), args.include):
                continue
            work.append((src, pp.join(dest_dir, os.path.basename(src))))
        else:
            raise SystemExit(f"no such file or directory: {src}")

    q = []
    if args.collection:
        q.append(f"collection={urllib.parse.quote(args.collection)}")
    if args.replication:
        q.append(f"replication={urllib.parse.quote(args.replication)}")
    if args.ttl:
        q.append(f"ttl={urllib.parse.quote(args.ttl)}")
    suffix = ("?" + "&".join(q)) if q else ""

    def put(item):
        local, remote = item
        size = os.path.getsize(local)
        mime = mimetypes.guess_type(local)[0] or \
            "application/octet-stream"
        # stream the file object: -c workers each holding a whole
        # file in RAM would OOM on volume-sized inputs
        with open(local, "rb") as f:
            http_call("PUT",
                      f"http://{filer}"
                      f"{urllib.parse.quote(remote)}{suffix}",
                      f, {"Content-Type": mime,
                          "Content-Length": str(size)}, timeout=600)
        return remote, size

    copied = errors = 0
    with ThreadPoolExecutor(max_workers=args.c) as pool:
        for fut in [pool.submit(put, item) for item in work]:
            try:
                remote, n = fut.result()
                copied += 1
                print(f"{remote} ({n} bytes)")
            except Exception as e:  # noqa: BLE001 - per-file report
                errors += 1
                print(f"ERROR: {e}", file=sys.stderr)
    print(f"copied {copied} files to {filer}{dest_dir}"
          + (f", {errors} failed" if errors else ""))
    if errors:
        raise SystemExit(1)


def cmd_filer_replicate(args):
    import json
    from ..replication import (EventSubscriber, FilerSource, Replicator,
                               make_sink)
    with open(args.config) as f:
        cfg = json.load(f)
    src_cfg = cfg["source"]
    source = FilerSource(src_cfg["filer"], src_cfg["master"],
                         path_prefix=src_cfg.get("path", "/"))
    sink = make_sink(cfg["sink"])
    rep = Replicator(source, sink)
    # the replicator still routes by source.path_prefix; the server-side
    # prefix just keeps foreign-path event batches off the wire
    sub = EventSubscriber(src_cfg["filer"], since=args.since,
                          path_prefix=(source.path_prefix
                                       if source.path_prefix != "/"
                                       else ""))
    print(f"replicating {src_cfg['filer']}{source.path_prefix} "
          f"-> {sink.kind} sink", flush=True)
    import time as _time
    from ..server.http_util import HttpError
    try:
        while True:
            try:
                # cursor advances only after the batch fully applies —
                # a down sink must stall replication, not skip events
                batch = sub.poll_once(advance=False)
            except HttpError:
                _time.sleep(1.0)
                continue
            for e in batch:
                delay = 1.0
                while True:
                    try:
                        action = rep.replicate(e["event"])
                        break
                    except Exception as err:
                        print(f"RETRY in {delay:.0f}s: {err}",
                              flush=True)
                        _time.sleep(delay)
                        delay = min(delay * 2, 30.0)
                if action != "skip":
                    path = (e["event"].get("newEntry")
                            or e["event"].get("oldEntry")
                            or {}).get("FullPath", "?")
                    print(f"{action} {path}", flush=True)
            sub.commit(batch)
    except KeyboardInterrupt:
        pass


def cmd_mount(args):
    from ..mount.fuse_ll import FuseError, FuseMount
    from ..mount.wfs import WeedFS
    try:
        fs = WeedFS(args.filer, master_url=args.master,
                    chunk_size=args.chunkSizeLimitMB << 20,
                    collection=args.collection,
                    replication=args.replication,
                    root_path=args.filerPath)
        mount = FuseMount(fs, args.dir, allow_other=args.allowOthers)
    except FuseError as e:
        raise SystemExit(str(e))
    print(f"mounting {args.filer} at {args.dir}", flush=True)
    _spawn_unmount_watchdog(args.dir)
    raise SystemExit(mount.run())


def _spawn_unmount_watchdog(mountpoint):
    """Exit the process once the mountpoint is externally unmounted.

    Normally libfuse's event loop returns ENODEV after `fusermount -u`
    and `mount.run()` exits on its own; on some kernels (observed on the
    4.4-era sandbox this ships in) the read on /dev/fuse blocks forever
    instead. Detection must happen OUTSIDE this process: from inside the
    FUSE server, both /proc/self/mounts (mount-namespace lock) and
    stat-based os.path.ismount (GETATTR racing mount setup) were observed
    to block indefinitely. So spawn a tiny watcher subprocess that polls
    /proc/mounts and TERM-then-KILLs us once the mountpoint entry has
    appeared and then disappeared. The watcher exits on its own if we die
    first, and stands down if the mount never appears (startup failure is
    mount.run()'s to report).
    """
    # /proc/mounts records the symlink-resolved path, octal-escaping
    # space, tab, newline and backslash.
    esc = (os.path.realpath(mountpoint)
           .replace("\\", "\\134").replace(" ", "\\040")
           .replace("\t", "\\011").replace("\n", "\\012"))

    def count_entries():
        try:
            with open("/proc/mounts") as f:
                return sum(1 for line in f
                           if len(p := line.split()) > 1 and p[1] == esc)
        except OSError:
            return -1

    # Baseline BEFORE any FUSE activity (a pre-existing bind/tmpfs mount
    # at the same target must not satisfy "our mount appeared", nor keep
    # "our mount is gone" false after fusermount -u removes only ours).
    # Taken in the parent so the watcher can't race mount.run().
    baseline = count_entries()
    if baseline < 0:
        return   # no usable /proc/mounts; watchdog can't help here
    watcher_src = r"""
import os, signal, sys, time
esc, pid, baseline = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

def alive():
    try:
        os.kill(pid, 0)
        return True
    except OSError:
        return False

def count():
    try:
        with open("/proc/mounts") as f:
            return sum(1 for line in f
                       if len(p := line.split()) > 1 and p[1] == esc)
    except OSError:
        return baseline + 1   # can't tell; don't kill a healthy mount

deadline = time.monotonic() + 30
while time.monotonic() < deadline and count() <= baseline:
    if not alive():
        sys.exit(0)
    time.sleep(0.2)
if count() <= baseline:
    sys.exit(0)       # never mounted; not ours to clean up
while count() > baseline:
    if not alive():
        sys.exit(0)
    time.sleep(0.5)
time.sleep(2.0)       # grace: let fuse_main return on its own
for sig in (signal.SIGTERM, signal.SIGKILL):
    if not alive():
        sys.exit(0)
    try:
        os.kill(pid, sig)
    except OSError:
        sys.exit(0)
    time.sleep(2.0)
"""
    import subprocess
    try:
        # -S: the watcher is stdlib-only; skip the site module (a
        # start-up hook there can pull heavyweight deps).
        subprocess.Popen(
            [sys.executable, "-S", "-c", watcher_src, esc,
             str(os.getpid()), str(baseline)],
            stdin=subprocess.DEVNULL, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
    except OSError:
        pass   # watchdog is best-effort; never block the mount itself


def cmd_msg_broker(args):
    from ..server.msg_broker import MsgBrokerServer
    b = MsgBrokerServer(port=args.port, host=args.ip).start()
    print(f"message broker on {b.url}")
    _wait(b)


def cmd_scaffold(args):
    from .scaffold import print_scaffold
    print(print_scaffold(args.config), end="")


def cmd_version(args):
    from .. import VERSION
    print(f"seaweedfs_tpu {VERSION}")


def _wait(*stoppables):
    """Park until SIGTERM/SIGINT, then stop servers gracefully
    (reference util/signal_handling.go OnInterrupt) — a clean volume
    server shutdown sends /cluster/goodbye so watch subscribers reroute
    immediately instead of waiting out heartbeat expiry."""
    done = threading.Event()

    def on_signal(signum, frame):
        done.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, on_signal)
        except (ValueError, OSError):
            pass
    try:
        done.wait()
    except KeyboardInterrupt:
        pass
    for s_ in stoppables:
        try:
            s_.stop()
        except Exception:  # noqa: BLE001 - best-effort teardown
            pass


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="weed-tpu")
    p.add_argument("-v", type=int, default=0,
                   help="glog verbosity level")
    p.add_argument("-vmodule", default="",
                   help="per-module verbosity, e.g. volume_server=3")
    sub = p.add_subparsers(dest="command", required=True)

    m = sub.add_parser("master", help="start a master server")
    m.add_argument("-port", type=int, default=9333)
    m.add_argument("-ip", default="127.0.0.1")
    m.add_argument("-volumeSizeLimitMB", type=int, default=30 * 1024)
    m.add_argument("-defaultReplication", default="000")
    m.add_argument("-pulseSeconds", type=int, default=5)
    m.add_argument("-jwtKey", default="",
                   help="HS256 key for per-fid write tokens")
    m.add_argument("-tlsCert", default="")
    m.add_argument("-tlsKey", default="")
    m.add_argument("-tlsCa", default="")
    m.add_argument("-tlsMutual", action="store_true",
                   help="require CA-verified client certs "
                        "on cluster-internal routes")
    m.add_argument("-peers", default="",
                   help="comma-separated master peers for raft HA, "
                        "e.g. host1:9333,host2:9333,host3:9333")
    m.add_argument("-mdir", default="",
                   help="directory for raft state persistence")
    m.add_argument("-maintenanceScripts", default="",
                   help="';'-separated shell command lines cron'd on "
                        "the leader (reference master.maintenance), "
                        'e.g. "volume.vacuum; ec.rebuild"')
    m.add_argument("-maintenanceIntervalSeconds", type=float,
                   default=17 * 60)
    m.add_argument("-whiteList", default="",
                   help="comma-separated IPs/CIDRs allowed on the "
                        "user-facing API (reference -whiteList). "
                        "Include your volume servers/filers/gateways: "
                        "only heartbeat/goodbye/raft stay open")
    m.add_argument("-metrics.address", dest="metricsAddress", default="",
                   help="Prometheus push-gateway address broadcast to "
                        "volume servers (reference -metrics.address)")
    m.add_argument("-metrics.intervalSeconds", dest="metricsInterval",
                   type=int, default=15)
    m.add_argument("-vacuumIntervalSeconds", type=float, default=15 * 60,
                   help="automatic vacuum + TTL-expiry sweep on the "
                        "leader (0 disables; reference "
                        "StartRefreshWritableVolumes)")
    m.add_argument("-garbageThreshold", type=float, default=0.3)
    m.add_argument("-sequencer", default="auto",
                   choices=["auto", "etcd"],
                   help="file-key sequencer: auto = in-memory "
                        "(raft-granted when -peers is set); etcd = "
                        "CAS blocks on an external etcd "
                        "(reference etcd_sequencer.go)")
    m.add_argument("-sequencerEtcd", default="127.0.0.1:2379",
                   help="etcd endpoint for -sequencer etcd")
    m.add_argument("-sequencerEtcdUser", default="")
    m.add_argument("-sequencerEtcdPassword", default="")
    m.set_defaults(fn=cmd_master)

    v = sub.add_parser("volume", help="start a volume server")
    v.add_argument("-port", type=int, default=8080)
    v.add_argument("-ip", default="127.0.0.1")
    v.add_argument("-dir", default="./data")
    v.add_argument("-max", default="7")
    v.add_argument("-mserver", default="127.0.0.1:9333")
    v.add_argument("-dataCenter", default="")
    v.add_argument("-rack", default="")
    v.add_argument("-pulseSeconds", type=int, default=5)
    v.add_argument("-ec.backend", dest="ec_backend", default="auto",
                   choices=list(EC_BACKENDS), help=EC_BACKEND_HELP)
    v.add_argument("-fastPort", type=int, default=0,
                   help="native C++ read plane port (0 = auto-pick, "
                        "-1 = disabled); plain needle GETs are served "
                        "there without the Python GIL")
    v.add_argument("-publicUrl", default="",
                   help="publicly accessible address advertised to "
                        "clients (reference -publicUrl)")
    v.add_argument("-read.redirect", dest="readRedirect",
                   default="true", choices=["true", "false"],
                   help="redirect reads for non-local volumes to a "
                        "replica (reference -read.redirect)")
    v.add_argument("-fileSizeLimitMB", type=int, default=256,
                   help="reject uploads above this size, 0 = no limit "
                        "(reference -fileSizeLimitMB)")
    v.add_argument("-compactionMBps", type=int, default=None,
                   help="limit background compaction or copying speed "
                        "in mega bytes per second (reference "
                        "-compactionMBps): vacuum's copy, every byte an "
                        "ec.rebuild pulls from another server, "
                        "volume.copy, ec.copy; 0 = unthrottled; not "
                        "given: SW_COMPACTION_MBPS")
    v.add_argument("-index", default="memory",
                   choices=["memory", "compact", "sortedfile", "disk"],
                   help="needle map variant (reference -index flag): "
                        "memory dict, 16B/needle compact arrays, "
                        "mmap'd sorted file, or a disk-backed writable "
                        "map for indexes larger than RAM (reference "
                        "-index leveldb). A read-only volume of a memory "
                        "index holds the compact arrays until it is "
                        "made writable again")
    v.add_argument("-cpuprofile", default="",
                   help="write an all-thread collapsed-stack CPU "
                        "profile here on shutdown (flamegraph.pl/"
                        "speedscope format; reference -cpuprofile)")
    v.add_argument("-jwtKey", default="")
    v.add_argument("-tlsCert", default="")
    v.add_argument("-tlsKey", default="")
    v.add_argument("-tlsCa", default="")
    v.add_argument("-tlsMutual", action="store_true",
                   help="require CA-verified client certs "
                        "on cluster-internal routes")
    v.add_argument("-whiteList", default="",
                   help="comma-separated IPs/CIDRs allowed to call")
    v.add_argument("-tierConfig", default="",
                   help="JSON file of remote tier backends, e.g. "
                        '{"s3": {"default": {"endpoint": ..., '
                        '"bucket": ...}}}')
    v.set_defaults(fn=cmd_volume)

    s = sub.add_parser("server", help="master + volume (+filer) combined")
    s.add_argument("-cpuprofile", default="",
                   help="write an all-thread collapsed-stack CPU "
                        "profile here on shutdown")
    s.add_argument("-ip", default="127.0.0.1")
    s.add_argument("-masterPort", type=int, default=9333)
    s.add_argument("-port", type=int, default=8080)
    s.add_argument("-dir", default="./data")
    s.add_argument("-max", default="50",
                   help="volume slots per directory")
    s.add_argument("-defaultReplication", default="000")
    s.add_argument("-dataCenter", default="")
    s.add_argument("-rack", default="")
    s.add_argument("-pulseSeconds", type=int, default=5)
    s.add_argument("-filer", action="store_true")
    s.add_argument("-filerPort", type=int, default=8888)
    s.add_argument("-s3", action="store_true")
    s.add_argument("-s3Port", type=int, default=8333)
    s.add_argument("-s3Config", default="",
                   help="IAM identities JSON (reference s3 config shape)")
    s.add_argument("-webdav", action="store_true")
    s.add_argument("-webdavPort", type=int, default=7333)
    s.add_argument("-ec.backend", dest="ec_backend", default="auto",
                   choices=list(EC_BACKENDS), help=EC_BACKEND_HELP)
    s.add_argument("-fastPort", type=int, default=0,
                   help="native C++ read plane port (0 = auto-pick, "
                        "-1 = disabled)")
    s.add_argument("-jwtKey", default="")
    s.add_argument("-tlsCert", default="")
    s.add_argument("-tlsKey", default="")
    s.add_argument("-tlsCa", default="")
    s.add_argument("-tlsMutual", action="store_true",
                   help="require CA-verified client certs "
                        "on cluster-internal routes")
    s.add_argument("-tierConfig", default="")
    s.set_defaults(fn=cmd_server)

    f = sub.add_parser("filer", help="start a filer server")
    f.add_argument("-port", type=int, default=8888)
    f.add_argument("-ip", default="127.0.0.1")
    f.add_argument("-master", default="127.0.0.1:9333")
    f.add_argument("-store", default="sqlite",
                   choices=["memory", "sqlite", "sharded", "redis",
                            "mysql", "postgres", "cassandra", "etcd"])
    f.add_argument("-db", default="./filer.db",
                   help="metadata path: a sqlite file, or a directory "
                        "of shard dbs for -store sharded (default "
                        "./filer_meta there)")
    f.add_argument("-storeShards", type=int, default=8,
                   help="shard count for -store sharded (sticky per "
                        "store directory)")
    f.add_argument("-redisAddr", default="127.0.0.1:6379",
                   help="redis endpoint for -store redis")
    f.add_argument("-redisPassword", default="")
    f.add_argument("-redisDb", type=int, default=0)
    f.add_argument("-mysqlAddr", default="127.0.0.1:3306",
                   help="mysql endpoint for -store mysql")
    f.add_argument("-mysqlUser", default="root")
    f.add_argument("-mysqlPassword", default="")
    f.add_argument("-mysqlDatabase", default="seaweedfs")
    f.add_argument("-postgresAddr", default="127.0.0.1:5432",
                   help="postgres endpoint for -store postgres")
    f.add_argument("-postgresUser", default="postgres")
    f.add_argument("-postgresPassword", default="")
    f.add_argument("-postgresDatabase", default="seaweedfs")
    f.add_argument("-cassandraAddr", default="127.0.0.1:9042",
                   help="cassandra endpoint for -store cassandra")
    f.add_argument("-cassandraUser", default="")
    f.add_argument("-cassandraPassword", default="")
    f.add_argument("-cassandraKeyspace", default="seaweedfs")
    f.add_argument("-etcdAddr", default="127.0.0.1:2379",
                   help="etcd endpoint for -store etcd (v3 JSON "
                        "gateway)")
    f.add_argument("-etcdUser", default="")
    f.add_argument("-etcdPassword", default="")
    f.add_argument("-collection", default="")
    f.add_argument("-defaultReplicaPlacement", default="")
    f.add_argument("-maxMB", type=int, default=32,
                   help="autochunk split size")
    f.add_argument("-s3", action="store_true")
    f.add_argument("-s3Port", type=int, default=8333)
    f.add_argument("-s3Config", default="")
    f.add_argument("-jwtKey", default="")
    f.add_argument("-tlsCert", default="")
    f.add_argument("-tlsKey", default="")
    f.add_argument("-tlsCa", default="")
    f.add_argument("-tlsMutual", action="store_true",
                   help="require CA-verified client certs "
                        "on cluster-internal routes")
    f.add_argument("-encryptVolumeData", action="store_true",
                   help="AES-256-GCM encrypt chunk data; volume servers "
                        "only see ciphertext (reference filer.toml "
                        "cipher)")
    f.add_argument("-compress", action="store_true",
                   help="gzip compressible chunks before storing")
    f.set_defaults(fn=cmd_filer)

    s3 = sub.add_parser("s3", help="standalone S3 gateway over a filer")
    s3.add_argument("-port", type=int, default=8333)
    s3.add_argument("-ip", default="127.0.0.1")
    s3.add_argument("-filer", default="127.0.0.1:8888")
    s3.add_argument("-master", default="",
                    help="master url (default: ask the filer)")
    s3.add_argument("-config", default="",
                    help="IAM identities JSON")
    s3.set_defaults(fn=cmd_s3)

    w = sub.add_parser("webdav", help="WebDAV gateway over a filer")
    w.add_argument("-port", type=int, default=7333)
    w.add_argument("-ip", default="127.0.0.1")
    w.add_argument("-filer", default="127.0.0.1:8888")
    w.add_argument("-master", default="")
    w.add_argument("-collection", default="")
    w.add_argument("-maxMB", type=int, default=8)
    w.set_defaults(fn=cmd_webdav)

    sh = sub.add_parser("shell", help="admin shell")
    sh.add_argument("-master", default="127.0.0.1:9333")
    sh.add_argument("-filer", default="",
                    help="filer host:port for fs.* commands")
    sh.add_argument("-c", default="", help="run one command and exit")
    sh.set_defaults(fn=cmd_shell)

    b = sub.add_parser("benchmark", help="cluster load test")
    b.add_argument("-master", default="127.0.0.1:9333")
    b.add_argument("-n", type=int, default=1024)
    b.add_argument("-size", type=int, default=1024)
    b.add_argument("-c", type=int, default=16)
    b.add_argument("-collection", default="benchmark")
    b.add_argument("-assignBatch", type=int, default=1,
                   help="files per master assign (?count= + fid_N "
                        "suffixes): >1 amortizes assign round trips "
                        "so the tool measures the data plane, not "
                        "its own per-file assign chatter")
    b.add_argument("-cpuprofile", default="",
                   help="write an all-thread collapsed-stack CPU "
                        "profile of the run (reference benchmark "
                        "-cpuprofile)")
    b.add_argument("-native", action="store_true",
                   help="drive the cluster with the C++ keep-alive "
                        "load engine (duration-based): measures server "
                        "capacity instead of this client's own ceiling")
    b.add_argument("-seconds", type=float, default=10.0,
                   help="per-phase duration for -native")
    b.add_argument("-pool", type=int, default=4096,
                   help="assigned-fid pool size for -native")
    b.set_defaults(fn=cmd_benchmark)

    u = sub.add_parser("upload", help="upload files")
    u.add_argument("-master", default="127.0.0.1:9333")
    u.add_argument("-collection", default="")
    u.add_argument("-replication", default="")
    u.add_argument("-ttl", default="")
    u.add_argument("-maxMB", type=int, default=32,
                   help="files above this split into chunk needles "
                        "behind a manifest fid (reference submit.go)")
    u.add_argument("files", nargs="+")
    u.set_defaults(fn=cmd_upload)

    d = sub.add_parser("download", help="download files by fid")
    d.add_argument("-master", default="127.0.0.1:9333")
    d.add_argument("-dir", default=".",
                   help="output directory (reference download -dir); "
                        "files keep their stored names when present")
    d.add_argument("fids", nargs="+")
    d.set_defaults(fn=cmd_download)

    wt = sub.add_parser("watch", help="follow a filer's metadata events")
    wt.add_argument("-filer", default="127.0.0.1:8888")
    wt.add_argument("-since", type=float, default=0.0,
                    help="resume from this event timestamp")
    wt.add_argument("-pathPrefix", default="",
                    help="only events under this path prefix "
                         "(reference watch -pathPrefix; filtered "
                         "server-side)")
    wt.set_defaults(fn=cmd_watch)

    fc = sub.add_parser("filer.copy",
                        help="copy local files/dirs into the filer")
    fc.add_argument("paths", nargs="+",
                    help="src... then http://filer:8888/dest/dir/")
    fc.add_argument("-include", default="",
                    help="glob of file names to copy, e.g. *.pdf")
    fc.add_argument("-collection", default="")
    fc.add_argument("-replication", default="")
    fc.add_argument("-ttl", default="")
    fc.add_argument("-c", type=int, default=8,
                    help="concurrent file uploads")
    fc.set_defaults(fn=cmd_filer_copy)

    fr = sub.add_parser("filer.replicate",
                        help="continuously replicate filer changes to a "
                             "sink (another filer or an S3 bucket)")
    fr.add_argument("-config", required=True,
                    help='JSON: {"source": {"filer":..., "master":..., '
                         '"path":...}, "sink": {"type": "filer"|"s3", '
                         '...}}')
    fr.add_argument("-since", type=float, default=0.0)
    fr.set_defaults(fn=cmd_filer_replicate)

    bk = sub.add_parser("backup",
                        help="incremental local copy of a live volume")
    bk.add_argument("-server", default="127.0.0.1:9333",
                    help="master url")
    bk.add_argument("-dir", default=".")
    bk.add_argument("-volumeId", type=int, required=True)
    bk.add_argument("-collection", default="")
    bk.set_defaults(fn=cmd_backup)

    se = sub.add_parser("see",
                        help="dump .dat/.idx records as text (reference "
                             "see_dat/see_idx debug tools)")
    se.add_argument("file", help="path to a .dat or .idx file")
    se.add_argument("-offsetWidth", type=int, default=4,
                    choices=[4, 5], help="idx entry offset width")
    se.add_argument("-limit", type=int, default=0,
                    help="stop after N records (0 = all)")
    se.set_defaults(fn=cmd_see)

    ex = sub.add_parser("export", help="export volume needles to tar")
    ex.add_argument("-dir", default=".")
    ex.add_argument("-volumeId", type=int, required=True)
    ex.add_argument("-collection", default="")
    ex.add_argument("-o", default="", help="tar output path")
    ex.set_defaults(fn=cmd_export)

    fx = sub.add_parser("fix", help="rebuild .idx from .dat")
    fx.add_argument("-dir", default=".")
    fx.add_argument("-volumeId", type=int, required=True)
    fx.add_argument("-collection", default="")
    fx.set_defaults(fn=cmd_fix)

    cp = sub.add_parser("compact", help="force-vacuum a local volume")
    cp.add_argument("-dir", default=".")
    cp.add_argument("-volumeId", type=int, required=True)
    cp.add_argument("-collection", default="")
    cp.add_argument("-method", type=int, default=1, choices=[0, 1],
                    help="0 = scan the .dat (reference Compact), "
                         "1 = copy by the index (reference Compact2)")
    cp.set_defaults(fn=cmd_compact)

    mt = sub.add_parser("mount", help="FUSE-mount the filer namespace")
    mt.add_argument("-filer", default="127.0.0.1:8888")
    mt.add_argument("-master", default="",
                    help="master url (default: ask the filer)")
    mt.add_argument("-dir", required=True, help="mount point")
    mt.add_argument("-collection", default="")
    mt.add_argument("-replication", default="")
    mt.add_argument("-chunkSizeLimitMB", type=int, default=8)
    mt.add_argument("-allowOthers", action="store_true")
    mt.add_argument("-filer.path", dest="filerPath", default="/",
                    help="mount this remote subtree of the filer "
                         "namespace (reference mount -filer.path)")
    mt.set_defaults(fn=cmd_mount)

    mb = sub.add_parser("msgBroker", help="message queue broker")
    mb.add_argument("-port", type=int, default=17777)
    mb.add_argument("-ip", default="127.0.0.1")
    mb.set_defaults(fn=cmd_msg_broker)

    sc = sub.add_parser("scaffold", help="print example config files")
    sc.add_argument("-config", default="replication",
                    choices=["tier", "s3", "replication", "security",
                             "notification", "filer", "master"])
    sc.set_defaults(fn=cmd_scaffold)

    ver = sub.add_parser("version", help="print version")
    ver.set_defaults(fn=cmd_version)
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    from ..util import glog
    glog.set_verbosity(args.v)
    if args.vmodule:
        glog.set_vmodule(args.vmodule)
    _apply_tls_config(args)
    args.fn(args)


if __name__ == "__main__":
    main()
