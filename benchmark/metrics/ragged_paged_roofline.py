"""Kernels — ``ops/pallas/ragged_paged_attention.py``: the ragged paged
attention kernel's share of its roofline in the traced seconds.

Device time: the kernel's events in the trace (``custom-call(`` events named
``closed_call`` whose result is ``f32[heads, 1, packed tokens, head size]``;
the program gives its kernels no names yet).  Useful work: what the requests
that were served in those seconds needed, counted from the client's side by
``lib/kernel_costs.py::ragged_paged_attention`` — every token that reached
the client in the traced seconds attended its whole context once per layer,
and every request whose first token arrived there had its prompt prefilled
in chunks of ``PENROZ_PREFILL_CHUNK``.  GPT-2-large made through ``/model/``
serves in float32: 4-byte queries and KV pages.  Padding in a fused tick
(descriptor blocks and steps beyond the work) is time the kernel spends and
work it does not do: it lowers the share, as it should."""

from benchmark.lib import kernel_costs, trace_reduce

PREFILL_CHUNK = 256      # the program's default, unless the launch env says


def _useful_work(requests, t0: float, t1: float, chunk: int):
    q = attended = read = 0
    for r in requests:
        p = len(r.prompt)
        if r.token_at and t0 <= r.token_at[0] < t1:     # prefilled here
            start = 0
            while start < p:
                size = min(chunk, p - start)
                q += size
                attended += size * start + size * (size + 1) // 2
                read += start + size
                start += size
        for i, t in enumerate(r.token_at[1:], start=1):  # decode steps
            if t0 <= t < t1:
                q += 1
                attended += p + i
                read += p + i
    return q, attended, read


def read(art):
    trace = art.get("trace")
    if art.get("kind") != "serve_open" or not trace or not art.get("peaks"):
        return None
    d, win = art["dims"], art["window"]
    head = d["d"] // d["heads"]
    kernel = trace_reduce.kernel_time(
        trace["planes"], trace["w0"], trace["w1"],
        {"name": r"^%closed_call",
         "result": rf"^\(?f32\[{d['heads']},1,\d+,{head}\]"})
    if not kernel["calls"]:
        return None
    chunk = int(art["cfg"].get("launch_env", {}).get("PENROZ_PREFILL_CHUNK",
                                                     PREFILL_CHUNK))
    q, attended, kv_read = _useful_work(win["requests"],
                                        win["trace_obj"].t0,
                                        win["trace_obj"].t1, chunk)
    if not q:
        return None
    cost = kernel_costs.ragged_paged_attention(q, attended, kv_read,
                                               d["heads"], head, 4)
    least = d["depth"] * kernel_costs.roofline_seconds(cost, art["peaks"])[0]
    return 100.0 * least / kernel["seconds"]
