"""Model runtime: wall time of a scheduler tick that carries one or more
prefill chunks beside the decoding rows, the median over the window's such
ticks from the engine's tick timeline (``kinds/serve_open.py::_ticks``):
every step of such a tick is padded to the chunk's descriptor blocks, so
this is what a tick sized step by step would shrink."""


def read(art):
    if art.get("kind") != "serve_open":
        return None
    return art["window"]["ticks"]["with_prefill"]["ms_p50"]
