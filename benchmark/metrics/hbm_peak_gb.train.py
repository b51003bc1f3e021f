"""Cache and memory: the most the fullest chip held, training cells — JAX's
``memory_stats()``: the peak of live buffers, or live buffers while the
cell's work ran plus the largest scratch reservation of a running program
(``lib/program.py::memory_peak_bytes``), whichever is larger."""


def read(art):
    if art.get("kind") != "train" or not art.get("memory_peak_bytes"):
        return None
    return art["memory_peak_bytes"] / 1e9
