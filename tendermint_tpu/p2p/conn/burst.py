"""Burst knobs for the p2p frame plane.

One resolver shared by SecretConnection (vectored seal/open) and
MConnection (multi-packet drain per link write): burst mode and the max
packets coalesced per send burst. Resolution order is utils/knobs':
the TM_TPU_P2P_BURST env var always wins (an operator must
be able to pin a node's transport behavior regardless of config), then
whatever node.py wired from `config.base.p2p_burst*`, then defaults.

  TM_TPU_P2P_BURST=off   -> per-frame path, byte- and syscall-identical
                            to the pre-burst code (the escape hatch)
  TM_TPU_P2P_BURST=on    -> burst framing on, default max packets
  TM_TPU_P2P_BURST=auto  -> same as on (the burst path falls back to
                            per-frame crypto automatically when the
                            native kernels are unavailable)
  TM_TPU_P2P_BURST=<N>   -> on, with N packets max per send burst

Burst framing never changes the wire format: a burst is exactly the
concatenation of the frames the per-frame path would have produced, so
burst and non-burst nodes interoperate frame-for-frame.
"""

from __future__ import annotations

from typing import Tuple

from tendermint_tpu.utils import knobs

DEFAULT_MAX_PACKETS = 64  # ~64KB ceiling per sendall at 1KB frames

_cfg_mode: str = "auto"
_cfg_max: int = DEFAULT_MAX_PACKETS


def configure(mode: str = "auto", max_packets: int = 0) -> None:
    """Node-level wiring (config.base.p2p_burst / p2p_burst_max)."""
    global _cfg_mode, _cfg_max
    _cfg_mode = str(mode or "auto").strip().lower()
    _cfg_max = int(max_packets) if max_packets else DEFAULT_MAX_PACKETS


def resolve() -> Tuple[bool, int]:
    """-> (burst_enabled, max_packets_per_send_burst). Reads the env on
    every call so tests and subprocess harnesses can flip it without
    re-importing; connection setup calls this once per MConnection."""
    mode, max_packets = _cfg_mode, _cfg_max
    env = knobs.knob_str("TM_TPU_P2P_BURST")
    if env:
        if env.isdigit():
            mode, max_packets = "on", max(1, int(env))
        else:
            mode = env
    if mode in knobs.FALSY:
        return False, 1
    return True, max(1, max_packets)
