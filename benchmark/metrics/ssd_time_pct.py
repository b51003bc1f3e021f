"""Kernels — ``ops/ssm.py::ssd_chunked`` and ``ops/modules.py::Mamba2Mixer``:
the share of the traced epochs' busy device time spent in the state-space
mixers' scan and convolution.

The scan is ``jnp`` in chunks, no Pallas call, and a ``jax.named_scope`` does
not reach the device trace (its events are named by their HLO text and carry
no scope: found on the chip, PR 51), so the mixers' operations are told by
the **shapes of their results**, built from what the program says of itself
(``penroz/ssd_plan``: heads and groups held, head size, state, chunk, taps,
T) and the job's micro-batch:

- the scan's: an array of three or more dimensions larger than 1 whose first
  is the chunk count ``c = T / chunk`` (or ``c`` merged with the groups or
  the heads, as a batched product has it) and whose size is one of the
  scan's own: x and y a chunk (``T · H · P``), Δ and the log-decays (``T ·
  H``), B and C (``T · G · N``), the scores (``c · G · chunk²``), the decay
  mask (``c · H · chunk²``), the chunk states (``c · H · P · N``);
- the convolution's: an array with the convolution's channel count (``H · P
  + 2 · G · N``) among its dimensions beside T, T + taps − 1 or the taps.

Custom calls (the program's Pallas kernels) never count.  Counted is the self
time of every matching ``XLA Ops`` event inside the traced epochs, forward,
recomputed forward and backward alike, over the busy time.  Left out: the
mixer's two projections (matmuls, as any layer's), the gate and the gated
norm (``(T, H · P)``, a shape other layers share).  A program that records no
``penroz/ssd_plan`` gives nothing to read."""

import math
import re

from benchmark.lib import trace_reduce

_SHAPE = re.compile(r"\b(?:pred|[a-z]+\d+)\[([\d,]+)\]")


def signatures(plan: dict, batch: int) -> dict:
    """What :func:`is_mixer_op` looks for, from a ``penroz/ssd_plan`` span's
    counters and the micro-batch."""
    H, G, P, N = plan["held"], plan["groups"], plan["head_dim"], plan["state"]
    L, T = plan["chunk"], plan["T"]
    c = -(-T // L)
    tokens = batch * c * L
    return {
        "leading": {c, c * G, c * H, batch * c, batch * c * G, batch * c * H},
        "sizes": {tokens * H * P, tokens * H, tokens * G * N,
                  batch * c * G * L * L, batch * c * H * L * L,
                  batch * c * H * P * N},
        "channels": H * P + 2 * G * N,
        "beside": {T, T + plan["conv_kernel"] - 1, plan["conv_kernel"]}}


def is_mixer_op(name: str, sig: dict) -> bool:
    """Whether the ``XLA Ops`` event ``name`` (an instruction's HLO text)
    writes an array of the scan's or the convolution's."""
    if " custom-call(" in name:
        return False
    _, _, rest = name.partition(" = ")
    # the result's types: up to the operation's name, a tuple's in brackets
    result = rest[:rest.find(") ") + 1] if rest.startswith("(") \
        else rest.split("(", 1)[0]
    for dims in _SHAPE.findall(result):
        shape = [int(n) for n in dims.split(",")]
        big = [n for n in shape if n > 1]
        if (len(big) >= 3 and big[0] in sig["leading"]
                and math.prod(big) in sig["sizes"]):
            return True
        if sig["channels"] in big and any(n in sig["beside"] for n in big):
            return True
    return False


def read(art):
    trace, plan = art.get("trace"), art.get("ssd_plan")
    if art.get("kind") != "train" or not trace or not plan:
        return None
    sig = signatures(plan, art["job"]["batch_size"])
    w0, w1 = trace["w0"], trace["w1"]
    spent = sum(own
                for dev in trace["planes"]["devices"].values()
                for name, a, b, own in trace_reduce.self_times(dev["ops"])
                if w0 <= a and b <= w1 and is_mixer_op(name, sig))
    busy = trace["busy_s"] * trace["devices"]
    return 100.0 * spent / busy if busy else None
