"""Bytes of host arrays handed to the device per signature verified
there: `verifier_h2d_bytes_total` over the process verifier's
`jax_sigs`, both as they stand when the run ends (the counter has no
window; every verify dispatch of a traced run is in both)."""

from benchmark import program_spans

LAYER = "device kernels"
MOVES = "headers_per_s"


def read(r):
    from tendermint_tpu.models.verifier import default_verifier
    sent = program_spans.counter_total("verifier_h2d_bytes_total")
    sigs = default_verifier().stats["jax_sigs"]
    return sent / sigs if sent is not None and sigs else None
