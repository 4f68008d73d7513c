"""CLI (cmd/tendermint): init, node, version, show_validator,
gen_validator, unsafe_reset_all. Testnet/replay/lite commands land with
their subsystems."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time


def cmd_init(args) -> int:
    """Write genesis + priv validator + config skeleton (cmd init.go:48)."""
    from tendermint_tpu.types import GenesisDoc, PrivValidatorFile
    from tendermint_tpu.types.genesis import GenesisValidator
    home = args.home
    cfg_dir = os.path.join(home, "config")
    os.makedirs(cfg_dir, exist_ok=True)
    pv_path = os.path.join(cfg_dir, "priv_validator.json")
    pv = PrivValidatorFile.load_or_generate(pv_path)
    gen_path = os.path.join(cfg_dir, "genesis.json")
    if not os.path.exists(gen_path):
        gen = GenesisDoc(
            chain_id=args.chain_id or f"test-chain-{int(time.time())}",
            genesis_time_ns=time.time_ns(),
            validators=[GenesisValidator(pv.pubkey.ed25519, 10)])
        gen.save(gen_path)
        print(f"initialized genesis at {gen_path}")
    else:
        print(f"genesis already exists at {gen_path}")
    print(f"priv validator at {pv_path}")
    return 0


def cmd_node(args) -> int:
    """Run a node (cmd run_node.go). With --p2p it listens, dials
    configured peers and serves RPC; otherwise it is a self-contained
    single-process validator."""
    from tendermint_tpu.node import default_node
    from tendermint_tpu.abci.apps import CounterApp, KVStoreApp
    from tendermint_tpu.config import default_config
    from tendermint_tpu.utils.log import setup_logging
    setup_logging(default_config(args.home).base.log_level)
    app = {"kvstore": KVStoreApp, "counter": CounterApp}[args.app]()
    # TM_NODE_PROFILE=<path>: sampling profiler over EVERY thread
    # (SIGPROF at ~97 Hz of CPU time, sys._current_frames) — the
    # profiling story for multi-process testnets, where each node
    # samples itself and dumps top frames on shutdown. cProfile can't
    # do this (per-thread, and its tracing overhead skews the 1-core
    # contention being measured); the unsafe RPC profiler routes cover
    # interactive single-node use.
    prof_path = os.environ.get("TM_NODE_PROFILE")
    if prof_path:
        import collections
        import signal as _signal
        samples = collections.Counter()

        def _sample(signum, frame):
            # NOTE: samples EVERY thread's current frame per tick, so
            # parked threads surface as wait/accept/select rows —
            # read those as thread residency; the remaining rows are
            # the CPU story
            for fr in sys._current_frames().values():
                # leaf frame + its caller: enough to attribute cost
                co = fr.f_code
                caller = fr.f_back.f_code if fr.f_back else None
                samples[(co.co_filename, co.co_name,
                         caller.co_name if caller else "")] += 1

        _signal.signal(_signal.SIGPROF, _sample)
        _signal.setitimer(_signal.ITIMER_PROF, 0.0103, 0.0103)
        import atexit

        def _dump():
            _signal.setitimer(_signal.ITIMER_PROF, 0)
            total = sum(samples.values()) or 1
            with open(prof_path, "w") as f:
                f.write(f"# {total} samples (CPU time, all threads)\n")
                for (fn, name, caller), c in samples.most_common(60):
                    f.write(f"{100*c/total:6.2f}% {name} <- {caller} "
                            f"({fn})\n")
        atexit.register(_dump)
    if getattr(args, "state_sync", False):
        # env wins over config everywhere the knob plane reads — the
        # flag is sugar for exporting it before Node construction
        os.environ["TM_TPU_STATE_SYNC"] = "on"
    node = default_node(args.home, app=app, with_p2p=args.p2p,
                        fast_sync=(args.fast_sync if args.p2p else False))
    if args.p2p_laddr:
        node.config.p2p.laddr = args.p2p_laddr
    if args.rpc_laddr:
        node.config.rpc.laddr = args.rpc_laddr
        node.with_rpc = True
    if args.grpc_laddr:
        # gRPC only — does not turn on the HTTP JSON-RPC listener
        node.config.rpc.grpc_laddr = args.grpc_laddr
    if args.persistent_peers:
        node.config.p2p.persistent_peers = args.persistent_peers
    node.start()
    if node.switch is not None:
        print(f"p2p listening on {node.switch.listen_address}", flush=True)
    if node.rpc_address is not None:
        print(f"rpc listening on {node.rpc_address[0]}:"
              f"{node.rpc_address[1]}", flush=True)
    print(f"node started: chain={node.gen_doc.chain_id} "
          f"height={node.height}", flush=True)
    try:
        last = -1
        deadline = time.time() + args.max_seconds if args.max_seconds else None
        while True:
            time.sleep(0.2)
            fatal = node.consensus.fatal_error or getattr(
                getattr(node, "blockchain_reactor", None), "sync_error",
                None)
            if fatal is not None:
                # consensus OR fast-sync halted unrecoverably (the
                # reference panics): die loudly rather than sit at a
                # frozen height
                print(f"CONSENSUS FAILURE: {fatal!r}", flush=True)
                node.stop()
                return 1
            if node.height != last:
                last = node.height
                print(f"committed height={last} "
                      f"app_hash={node.consensus.state.app_hash.hex()[:16]}",
                      flush=True)
            if deadline and time.time() > deadline:
                break
            if args.max_height and node.height >= args.max_height:
                break
    except KeyboardInterrupt:
        pass
    node.stop()
    print(f"node stopped at height {node.height}")
    return 0


def cmd_show_validator(args) -> int:
    from tendermint_tpu.types import PrivValidatorFile
    pv = PrivValidatorFile.load(
        os.path.join(args.home, "config", "priv_validator.json"))
    print(json.dumps(pv.pubkey.to_obj()))
    return 0


def cmd_gen_validator(args) -> int:
    from tendermint_tpu.types.priv_validator import LocalSigner, PrivValidator
    from tendermint_tpu.types.keys import PrivKey
    key = PrivKey.generate()
    print(json.dumps({"priv_key": key.to_obj(),
                      "pub_key": key.pubkey.to_obj()}))
    return 0


def cmd_unsafe_reset_all(args) -> int:
    """Wipe data dir, keep genesis + reset priv validator height state."""
    data = os.path.join(args.home, "data")
    if os.path.isdir(data):
        shutil.rmtree(data)
        print(f"removed {data}")
    return cmd_unsafe_reset_priv_validator(args)


def cmd_unsafe_reset_priv_validator(args) -> int:
    """Reset ONLY the double-sign protection state (the reference's
    unsafe_reset_priv_validator, cmd reset_priv_validator.go) — for a
    validator that must re-join after losing its state, accepting the
    double-sign risk."""
    pv_path = os.path.join(args.home, "config", "priv_validator.json")
    if os.path.exists(pv_path):
        from tendermint_tpu.types import PrivValidatorFile
        pv = PrivValidatorFile.load(pv_path)
        pv.last_height = pv.last_round = pv.last_step = 0
        pv.last_sign_bytes = None
        pv.last_signature = None
        pv._persist()
        print(f"reset priv validator sign state at {pv_path}")
    return 0


def cmd_lite(args) -> int:
    """Light-client proxy daemon (cmd lite.go:60): serve a local RPC
    whose results are certified against the chain before returning."""
    from tendermint_tpu.lite import (
        HTTPProvider, InquiringCertifier, MemProvider, SecureClient,
        CacheProvider, FileProvider)
    from tendermint_tpu.rpc import JSONRPCClient, RPCServer

    rpc = JSONRPCClient(args.node_addr)
    source = HTTPProvider(rpc)
    trusted = source.get_by_height(args.trust_height) \
        if args.trust_height else source.latest_commit()
    if trusted is None:
        print("cannot fetch a trusted commit from the node")
        return 1
    # the node itself is layered in as the outermost provider: bisection
    # must be able to FETCH intermediate commits, not just read the cache
    store = CacheProvider(
        MemProvider(), FileProvider(os.path.join(args.home, "lite")),
        source)
    chain_id = args.chain_id or \
        rpc.call("genesis")["genesis"]["chain_id"]
    cert = InquiringCertifier(chain_id, trusted, store)
    sc = SecureClient(rpc, cert)

    server = RPCServer()
    server.register("block", lambda height=0: sc.block(int(height)))
    server.register("commit", lambda height=0: sc.commit(int(height)))
    server.register("validators",
                    lambda height=0: sc.validators(int(height)))
    server.register("status", sc.status)
    server.register("tx", lambda hash=b"", prove=True: sc.tx(hash))
    # unverifiable routes proxied straight through
    for route in ("broadcast_tx_sync", "broadcast_tx_async",
                  "broadcast_tx_commit", "abci_info", "net_info",
                  "genesis"):
        server.register(route,
                        (lambda r: lambda **kw: rpc.call(r, **kw))(route))
    from tendermint_tpu.node import _parse_laddr
    host, port = server.serve(*_parse_laddr(args.laddr))
    print(f"lite proxy serving on {host}:{port} "
          f"(trusting height {cert.last_height})", flush=True)
    deadline = time.time() + args.max_seconds if args.max_seconds else None
    try:
        while deadline is None or time.time() < deadline:
            time.sleep(0.5)
    except KeyboardInterrupt:
        pass
    server.stop()
    return 0


def cmd_version(args) -> int:
    from tendermint_tpu import __version__
    print(__version__)
    return 0


def cmd_probe_upnp(args) -> int:
    """cmd/tendermint/commands/probe_upnp.go: discover an IGD and report
    its capabilities as JSON."""
    import json as _json
    from tendermint_tpu.p2p import upnp
    try:
        report = upnp.probe(timeout=args.timeout)
    except upnp.UPnPError as e:
        print(_json.dumps({"ok": False, "error": str(e)}))
        return 1
    print(_json.dumps({"ok": True, "capabilities": report}))
    return 0


def cmd_show_node_id(args) -> int:
    from tendermint_tpu.p2p import NodeKey
    nk = NodeKey.load_or_generate(
        os.path.join(args.home, "config", "node_key.json"))
    print(nk.id())
    return 0


def cmd_replica(args) -> int:
    """Run an edge read replica (serving/edge.py): a follower node
    with NO validator key serving lite-certified reads."""
    from tendermint_tpu.serving.edge import run_replica
    return run_replica(args)


def cmd_shardset(args) -> int:
    """Run one sharded front-door process (serving/deploy.py)."""
    from tendermint_tpu.serving.deploy import run_shardset
    return run_shardset(args)


def cmd_worker(args) -> int:
    """Run several validators of a materialized topology in this one
    process (serving/worker.py)."""
    from tendermint_tpu.serving.worker import run_worker
    return run_worker(args)


def cmd_testnet(args) -> int:
    """Emit an N-validator testnet file tree (cmd testnet.go:97): a shared
    genesis listing every validator, per-node priv_validator + node_key +
    config.json with persistent_peers wired to all other nodes."""
    from tendermint_tpu.config import default_config, save_config
    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.types import GenesisDoc, PrivValidatorFile
    from tendermint_tpu.types.genesis import GenesisValidator

    n = args.n
    out = args.output or args.home
    chain_id = args.chain_id or f"testnet-{int(time.time())}"
    pvs, node_keys = [], []
    for i in range(n):
        home = os.path.join(out, f"node{i}")
        cfg_dir = os.path.join(home, "config")
        os.makedirs(cfg_dir, exist_ok=True)
        pvs.append(PrivValidatorFile.load_or_generate(
            os.path.join(cfg_dir, "priv_validator.json")))
        node_keys.append(NodeKey.load_or_generate(
            os.path.join(cfg_dir, "node_key.json")))
    gen = GenesisDoc(
        chain_id=chain_id, genesis_time_ns=time.time_ns(),
        validators=[GenesisValidator(pv.pubkey.ed25519, 10) for pv in pvs])
    base_port = args.base_port
    for i in range(n):
        home = os.path.join(out, f"node{i}")
        gen.save(os.path.join(home, "config", "genesis.json"))
        cfg = default_config(home)
        cfg.p2p.laddr = f"tcp://127.0.0.1:{base_port + 2 * i}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{base_port + 2 * i + 1}"
        cfg.p2p.addr_book_strict = False
        cfg.p2p.persistent_peers = ",".join(
            f"{node_keys[j].id()}@127.0.0.1:{base_port + 2 * j}"
            for j in range(n) if j != i)
        save_config(cfg)
    print(f"wrote {n}-node testnet (chain {chain_id}) under {out}")
    return 0


def cmd_replay(args, console: bool = False) -> int:
    """Step through the consensus WAL against a fresh state machine
    (consensus/replay_file.go:32 RunReplayFile). --console pauses for
    ENTER between messages and accepts 'quit'."""
    from tendermint_tpu.config import default_config
    from tendermint_tpu.consensus.replay import replay_messages, wal_tail_for
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc

    config = default_config(args.home)
    gen_doc = GenesisDoc.load(
        os.path.join(args.home, "config", "genesis.json"))
    # readonly WAL: a writable open would trim a live writer's
    # in-flight frame and corrupt the log. NOTE this protects the WAL
    # only — the node handshake still opens the state/block stores
    # writable (as the reference's replay_file does), so the tool is
    # for stopped nodes / copied data dirs, not a running node's home.
    print("replay: do not run against a RUNNING node's data dir "
          "(stores open writable; the WAL itself is opened read-only)",
          file=sys.stderr)
    node = Node(config, gen_doc, priv_validator=None, wal_readonly=True)
    cs, wal = node.consensus, node.wal
    height = cs.state.last_block_height
    # same tail selection as node-start catchup (incl. the legacy
    # genesis fallback) so this debugging tool reproduces the node
    from tendermint_tpu.storage import WALCorruptionError
    try:
        tail = wal_tail_for(wal, height)
    except (ValueError, WALCorruptionError) as e:
        print(f"cannot replay: {e}")
        node.stop()
        return 1
    if tail is None:
        print(f"WAL has no messages after height {height}")
        node.stop()
        return 1

    def before_submit(msg):
        if console:
            cmdline = input(
                f"> next: {msg.get('type')} (ENTER to apply, q to quit) ")
            if cmdline.strip().lower() in ("q", "quit"):
                return False
        return True

    def after_submit(msg):
        print(f"replayed {msg.get('type')} -> "
              f"H/R/S {cs.rs.height}/{cs.rs.round}/{int(cs.rs.step)}")

    # the feed loop itself is replay_messages — the SAME code node
    # startup runs, so what this tool shows is what recovery does
    n = replay_messages(cs, tail, before_submit=before_submit,
                        after_submit=after_submit)
    print(f"replayed {n} messages; final height {cs.rs.height}")
    node.stop()
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tendermint_tpu")
    p.add_argument("--home", default=os.path.expanduser("~/.tendermint_tpu"))
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("init", help="initialize genesis + priv validator")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(fn=cmd_init)

    sp = sub.add_parser("node", help="run a node")
    sp.add_argument("--app", default="kvstore",
                    choices=["kvstore", "counter"])
    sp.add_argument("--max-height", type=int, default=0)
    sp.add_argument("--max-seconds", type=float, default=0)
    sp.add_argument("--p2p", action="store_true",
                    help="run the networking stack")
    sp.add_argument("--no-fast-sync", dest="fast_sync",
                    action="store_false", default=True)
    sp.add_argument("--p2p-laddr", default="",
                    help="override p2p listen address")
    sp.add_argument("--rpc-laddr", default="",
                    help="serve RPC on this address")
    sp.add_argument("--grpc-laddr", default="",
                    help="serve the gRPC BroadcastAPI on this address")
    sp.add_argument("--persistent-peers", default="",
                    help="comma-separated id@host:port")
    sp.add_argument("--state-sync", action="store_true",
                    help="join via p2p snapshot restore (fresh nodes)")
    sp.set_defaults(fn=cmd_node)

    sp = sub.add_parser("testnet",
                        help="write an N-validator testnet file tree")
    sp.add_argument("--n", type=int, default=4)
    sp.add_argument("--output", default="")
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--base-port", type=int, default=46656)
    sp.set_defaults(fn=cmd_testnet)

    sp = sub.add_parser("replay", help="replay the consensus WAL")
    sp.set_defaults(fn=cmd_replay)

    sp = sub.add_parser("replay_console",
                        help="interactively replay the consensus WAL")
    sp.set_defaults(fn=lambda a: cmd_replay(a, console=True))

    sp = sub.add_parser("replica",
                        help="run an edge read replica (keyless "
                             "follower + lite-certified reads)")
    sp.add_argument("--app", default="kvstore",
                    choices=["kvstore", "counter"])
    sp.add_argument("--rpc-laddr", default="",
                    help="serve the replica RPC surface here")
    sp.add_argument("--persistent-peers", default="",
                    help="validators to follow (id@host:port,...)")
    sp.add_argument("--max-lag", type=int, default=0,
                    help="healthz staleness threshold in heights "
                         "(0 = TM_TPU_EDGE_MAX_LAG / default)")
    sp.add_argument("--max-seconds", type=float, default=0)
    sp.add_argument("--state-sync", action="store_true",
                    help="bootstrap from a peer snapshot before "
                         "tailing via fast sync")
    sp.set_defaults(fn=cmd_replica)

    sp = sub.add_parser("shardset",
                        help="run N chains behind one sharded RPC "
                             "front door in this process")
    sp.add_argument("--shards", type=int, default=2)
    sp.add_argument("--laddr", default="tcp://127.0.0.1:46657",
                    help="front-door RPC listen address")
    sp.add_argument("--max-seconds", type=float, default=0)
    sp.set_defaults(fn=cmd_shardset)

    sp = sub.add_parser("worker",
                        help="run several validators of a topology "
                             "(homes under --home) in this process")
    sp.add_argument("--nodes", required=True,
                    help="comma-separated home names under --home")
    sp.add_argument("--rpc", default="",
                    help="those of --nodes that serve RPC")
    sp.add_argument("--in-memory", action="store_true",
                    help="stores and signer state in memory")
    sp.add_argument("--max-seconds", type=float, default=0)
    sp.set_defaults(fn=cmd_worker)

    sp = sub.add_parser("lite", help="light-client RPC proxy")
    sp.add_argument("--node-addr", default="http://127.0.0.1:46657")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--trust-height", type=int, default=0)
    sp.add_argument("--max-seconds", type=float, default=0)
    sp.set_defaults(fn=cmd_lite)

    sub.add_parser("version").set_defaults(fn=cmd_version)

    sp = sub.add_parser("probe_upnp",
                        help="probe the local network for a UPnP IGD")
    sp.add_argument("--timeout", type=float, default=3.0)
    sp.set_defaults(fn=cmd_probe_upnp)
    sub.add_parser("show_validator").set_defaults(fn=cmd_show_validator)
    sub.add_parser("show_node_id").set_defaults(fn=cmd_show_node_id)
    sub.add_parser("gen_validator").set_defaults(fn=cmd_gen_validator)
    sub.add_parser("unsafe_reset_all").set_defaults(fn=cmd_unsafe_reset_all)
    sub.add_parser("unsafe_reset_priv_validator").set_defaults(
        fn=cmd_unsafe_reset_priv_validator)

    args = p.parse_args(argv)
    if args.cmd in ("node", "replica", "shardset", "lite"):
        # these verify signature batches, so on a TPU host they compile
        from tendermint_tpu.utils import compile_cache
        compile_cache.enable()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
