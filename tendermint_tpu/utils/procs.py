"""What a launcher of node processes needs before it spawns one: a
block of free ports and the children's environment. Used by
serving/deploy.py and the multi-process tests."""

from __future__ import annotations

import os
import random
import socket


def free_port_block(k: int) -> int:
    """A base port with k consecutively-bindable ports (multi-node
    harnesses need two per node; one busy port in the range reads as a
    consensus failure).

    Ports come from BELOW the kernel's ephemeral range (32768-60999 on
    this host): the probe-then-bind window is seconds long, and an
    outgoing connection's auto-assigned source port can steal a probed
    ephemeral-range port in between — the flaky 'Address already in
    use' node-boot failure."""
    for _ in range(50):
        base = random.randrange(20000, 32000, 2) | 1
        socks = []
        try:
            for off in range(k):
                s = socket.socket()
                s.bind(("127.0.0.1", base + off))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port block found")


def node_child_env(repo: str) -> dict:
    """Environment for spawned node processes: pinned to the CPU
    backend (a chip belongs to one process, and a parent that holds it
    can start CPU children — checked on the chip host, see the verify
    skill), without the compilation cache a CPU backend gets none of
    (utils/compile_cache)."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env
