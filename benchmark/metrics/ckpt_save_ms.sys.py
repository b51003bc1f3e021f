"""Data and checkpoints: the kernel's part of a periodic save's two host
passes, ``penroz/ckpt_encode`` + ``penroz/ckpt_write``: their
``host.sys_ms`` (mapping fresh pages for ``tobytes()``, writing to tmpfs),
the median over the window's periodic saves.  Beside ``.encode + .write``
it says whether the save is the copy or the allocation."""

from benchmark.lib import host_account


def read(art):
    return host_account.periodic_saves(art, host_account.SAVE_PASSES,
                                       lambda s: s.host["sys_ms"])
