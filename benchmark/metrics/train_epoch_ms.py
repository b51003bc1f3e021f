"""Model runtime: one optimizer step as the program times it — its
``penroz/train_epoch`` span, from the call of the epoch program to the cost
on the host — median over the epochs inside the window (all lie between
saves).  The inside twin of ``train_step_ms``, which also holds the batch
loading (``load_batch_ms``) and the loop around both."""

from benchmark.lib import program_spans


def read(art):
    return program_spans.span_ms(art, "penroz/train_epoch")
