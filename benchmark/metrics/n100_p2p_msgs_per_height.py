"""Messages an in-process validator's switch routed to its reactors per
height of the window (`tm_p2p_msgs_recv_total`, summed over its
`channel` label here: `program_spans.counter_total` reads unlabelled
families only)."""

from benchmark import readers

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def now():
    """The family's children added up; None where there is none."""
    from tendermint_tpu import telemetry
    fam = telemetry.REGISTRY.get("p2p_msgs_recv_total")
    if fam is None:
        return None
    return float(sum(child.value for _labels, child in fam.children()))


def read(r):
    a, b = (r.client.get(k, {}).get("msgs_recv")
            for k in ("n100_open", "n100_close"))
    heights = len(readers.window_blocks(r))
    nodes = r.client.get("in_process")
    if a is None or b is None or not heights or not nodes:
        return None
    return (b - a) / (heights * nodes)
