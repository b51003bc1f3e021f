"""Load generator (the benchmark's own): how late requests left the client,
sent minus due, 90th percentile over the window's requests.  A starved
generator must not be read as a fast server."""


def read(art):
    if art.get("kind") != "serve_open":
        return None
    return art["measured"]["gen_late_ms.p90"]
