"""Model runtime: model FLOP/s utilisation *while serving* — the FLOPs the
model needs for every token that reached a client in the window (a request's
first token stands for its prompt's prefill, every later one for a decode
step over its context; ``lib/serve_costs.py``), over the window's seconds
and the chip's published bf16 peak: ``serve_tokens_per_s``'s own span.
The whole step's share of the chip: it bounds what a later PR may claim in
a serving cell whatever kernel it takes off the path.  Padding of a fused
tick and the float32 passes of a matmul count nothing."""

from benchmark.lib import serve_costs


def read(art):
    if art.get("kind") != "serve_open" or not art.get("peaks"):
        return None
    d, win = art["dims"], art["window"]
    t0, t1 = win["t0"], win["t1"]
    flops = serve_costs.window_flops(win["requests"], t0, t1, d["d"],
                                     d["depth"], d["vocab"])
    seconds = t1 - t0
    if not flops or seconds <= 0:
        return None
    chips = art["device"]["count"]
    return 100.0 * flops / seconds / (chips * art["peaks"]["flops_bf16"])
