"""`cli worker`: several validators of a materialized Topology in one
process (serving/topology.py, `n_workers` > 0).

Each is a complete Node built from its own home under `--home`
(genesis, priv_validator, node_key, config.json), as `cli node` builds
one; with `--in-memory` its stores and its signer's state are in
memory and only the keys are read from the home. The nodes share the
interpreter, the process-wide verifier and nothing else: each has its
own event loop, mempool, stores and app, and they reach each other
over TCP like any other peers.

The process talks to the Deployment that started it over its standard
input and output, one JSON object a line:

    <- {"ready": [names], "pid": n}            once every node listens
    -> {"cmd": "dial"}                         every node dials its peers
    <- {"dialled": [names]}
    -> {"cmd": "report", "last": k}            (anything else: ignored)
    <- {"nodes": {name: {"height", "hashes": {h: [block, app]} for the
        last k heights, "peers": [ids], "banned": [ids], "id"}},
        "cpu_s": user + system seconds of this process}
    -> {"cmd": "stop"}                         stop the nodes, exit 0

It cannot outlive its parent: it asks the kernel for SIGKILL when the
parent dies (PR_SET_PDEATHSIG), exits at once when its standard input
reads end-of-file, which is what a dead parent's pipe does, and exits
in any case `--max-seconds` after it started.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

from tendermint_tpu.utils.procs import die_with_parent


def build_node(home: str, in_memory: bool, with_rpc: bool):
    """The complete Node of one home, as `cli node --p2p` builds it;
    `in_memory` keeps stores, WAL and the signer's last-sign state out
    of the home."""
    from tendermint_tpu.config import default_config
    from tendermint_tpu.node import Node
    from tendermint_tpu.p2p import NodeKey
    from tendermint_tpu.types import GenesisDoc, PrivValidatorFile
    from tendermint_tpu.types.priv_validator import (LocalSigner,
                                                     PrivValidator)
    cfg = default_config(home)
    gen = GenesisDoc.load(os.path.join(home, "config", "genesis.json"))
    pv = PrivValidatorFile.load(
        os.path.join(home, "config", "priv_validator.json"))
    if in_memory:
        pv = PrivValidator(LocalSigner(pv._privkey))
    node_key = NodeKey.load(os.path.join(home, "config", "node_key.json"))
    return Node(cfg, gen, priv_validator=pv, in_memory=in_memory,
                with_p2p=True, fast_sync=False, with_rpc=with_rpc,
                node_key=node_key)


def node_report(node, last: int) -> dict:
    """What a Deployment asks of every node: where it is, the block and
    app hashes of its last `last` heights, whom it holds and whom it
    has banned."""
    top = node.height
    hashes = {}
    for h in range(max(1, top - last + 1), top + 1):
        meta = node.block_store.load_block_meta(h)
        if meta is not None:
            hashes[str(h)] = [meta.block_id.hash.hex(),
                              meta.header.app_hash.hex()]
    sw = node.switch
    return {"id": sw.node_info.id, "height": top, "hashes": hashes,
            "peers": sorted(p.id for p in sw.peers.list()),
            "banned": sorted(pid for pid, rec in dict(sw.banned).items()
                             if rec["active"])}


def run_worker(args) -> int:
    die_with_parent()
    t0 = time.monotonic()
    names = [n for n in args.nodes.split(",") if n]
    rpc = {n for n in (args.rpc or "").split(",") if n}
    out = sys.stdout
    sys.stdout = sys.stderr         # a stray print must not reach the pipe
    from tendermint_tpu.config import default_config
    from tendermint_tpu.utils.log import setup_logging
    setup_logging(default_config(
        os.path.join(args.home, names[0])).base.log_level)

    def say(doc: dict) -> None:
        out.write(json.dumps(doc) + "\n")
        out.flush()

    if args.max_seconds:
        def overdue():
            time.sleep(max(0.0, args.max_seconds -
                           (time.monotonic() - t0)))
            os._exit(3)
        threading.Thread(target=overdue, daemon=True,
                         name="worker-deadline").start()

    nodes = {name: build_node(os.path.join(args.home, name),
                              args.in_memory, name in rpc)
             for name in names}
    # listen now, dial when told: by then every node of every worker
    # listens, so no dial comes too early and no outgoing connection
    # can have taken a port that a node has yet to bind
    for node in nodes.values():
        node.start(dial=False)
    say({"ready": names, "pid": os.getpid()})

    for line in sys.stdin:
        try:
            msg = json.loads(line)
        except ValueError:
            continue
        if msg.get("cmd") == "dial":
            for node in nodes.values():
                node.dial_configured_peers()
            say({"dialled": names})
        elif msg.get("cmd") == "report":
            cpu = os.times()
            say({"nodes": {name: node_report(node, int(msg.get("last", 8)))
                           for name, node in nodes.items()},
                 "cpu_s": cpu.user + cpu.system})
        elif msg.get("cmd") == "stop":
            stoppers = [threading.Thread(target=node.stop, daemon=True)
                        for node in nodes.values()]
            for t in stoppers:
                t.start()
            deadline = time.monotonic() + float(msg.get("within_s", 3.0))
            for t in stoppers:
                t.join(max(0.0, deadline - time.monotonic()))
            say({"stopped": names})
            os._exit(0)
    # end of input: the parent is gone, or has closed the pipe to say so
    os._exit(0)
