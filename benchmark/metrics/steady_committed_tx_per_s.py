"""Window writes whose commit the client learned of, over the window:
under the knee this is the offered rate, so it can show a loss and
never a gain."""

LAYER = "gossip and consensus rounds"
MOVES = "commit_p50_ms"


def read(r):
    w = r.client.get("window_s")
    return r.client["acked"] / w if w and "acked" in r.client else None
