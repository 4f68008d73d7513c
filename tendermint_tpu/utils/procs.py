"""What a launcher of node processes needs before it spawns one: a
block of free ports and the children's environment. Used by
serving/deploy.py and the multi-process tests.

    python -m tendermint_tpu.utils.procs <command> [arguments]

runs `command` in this process's place, as a child that cannot outlive
whoever started it (`die_with_parent`): for a child whose own code
knows nothing of its parent."""

from __future__ import annotations

import os
import random
import socket
import sys


def ephemeral_port_range() -> tuple:
    """(lo, hi) of the ports the kernel hands to outgoing connections
    (Linux's ip_local_port_range); (32768, 60999), its default, where
    it cannot be read."""
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            lo, hi = (int(x) for x in f.read().split()[:2])
        return lo, hi
    except (OSError, ValueError):
        return 32768, 60999


def free_port_block(k: int) -> int:
    """A base port with k consecutively-bindable ports (multi-node
    harnesses need two per node; one busy port in the range reads as a
    consensus failure).

    Ports come from OUTSIDE the kernel's ephemeral range, which is read
    and not assumed: the probe-then-bind window is seconds long, and an
    outgoing connection's auto-assigned source port can steal a probed
    ephemeral-range port in between — the flaky 'Address already in
    use' node-boot failure (seen on the chip's host in PR 31: two runs
    of nine lost a p2p listener to it while a hundred nodes dialled).
    Where the range leaves no room outside it, 20000-32000 as before."""
    lo, hi = ephemeral_port_range()
    if lo - 10000 >= k + 2:
        first, last = max(10000, lo - 22000), lo - k - 1
    elif 65535 - hi >= k + 2:
        first, last = hi + 1, 65535 - k
    else:
        first, last = 20000, 32000
    for _ in range(50):
        base = random.randrange(first, last, 2) | 1
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


def die_with_parent() -> None:
    """Ask the kernel to kill this process when the thread that started
    it dies (Linux's PR_SET_PDEATHSIG, which an exec keeps; nothing
    elsewhere), and go at once if it is dead already."""
    parent = os.getppid()
    try:
        import ctypes
        import signal
        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl(1, int(signal.SIGKILL), 0, 0, 0)     # PR_SET_PDEATHSIG
    except (OSError, AttributeError):
        return
    if os.getppid() != parent:
        os._exit(0)


if __name__ == "__main__":
    die_with_parent()
    os.execvp(sys.argv[1], sys.argv[1:])
