"""Model runtime: wall time of a scheduler tick in which every row decodes
and none prefills (one fused dispatch of ``superstep`` steps and the host
work around it), the median over the window's such ticks, each tick's own
time from the engine's tick timeline (``kinds/serve_open.py::_ticks``; the
``tick_ms`` histogram resolves to bucket edges 500 ms apart up there)."""


def read(art):
    if art.get("kind") != "serve_open":
        return None
    return art["window"]["ticks"]["decode_only"]["ms_p50"]
