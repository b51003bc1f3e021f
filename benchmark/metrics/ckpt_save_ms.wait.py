"""Data and checkpoints: what the saving thread *waited* inside a periodic
save's two host passes, ``penroz/ckpt_encode`` + ``penroz/ckpt_write``:
duration - ``host.cpu_ms``, the median over the window's periodic saves.
Near 0: the passes are the thread's own work (``ckpt_save_ms.sys`` says how
much of it the kernel's)."""

from benchmark.lib import host_account


def read(art):
    return host_account.periodic_saves(art, host_account.SAVE_PASSES,
                                       host_account.waited_ms)
