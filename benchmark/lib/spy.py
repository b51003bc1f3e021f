"""Host timestamps from inside the service, taken from the benchmark's files.

The service runs in this process, so a few of its entry points can be
wrapped for the length of a run (what a test's monkeypatch does, and what
``chip_smoke.py::Spy`` does for placement).  The wrappers only read the
clock and what passes through them; the one thing they *do* is end an
open-ended training job once the measurement is over.
"""

from __future__ import annotations

import threading
import time


class StopTraining(Exception):
    """Raised inside the training thread, by the epoch wrapper, once the
    benchmark has what it came for: /train/ has no cancel."""


class TrainSpy:
    """Observes one ``PUT /train/`` from inside: a timestamp at each epoch's
    end (after its cost is ready on the host) and at each save's start and
    end, the tokens each epoch consumed, and — from the first epoch only —
    the batch it trained on and the gradient it applied, for the comparison
    with the reference."""

    def __init__(self):
        self.epochs: list[tuple[float, int]] = []      # (end, tokens)
        self.costs: list[float] = []
        self.saves: list[tuple[float, float, bool]] = []  # (start, end, periodic)
        self.first_batch = None      # (xs, ys) numpy, epoch 1
        self.first_grad = None       # {param name: numpy}, epoch 1
        self.event = threading.Condition()
        self._stop = False
        self._undo = []

    # -- control ------------------------------------------------------------

    def stop_training(self):
        self._stop = True

    def periodic_save_ends(self) -> list[float]:
        with self.event:
            return [e for _, e, periodic in self.saves if periodic]

    def wait(self, timeout: float):
        with self.event:
            self.event.wait(timeout)

    # -- wrappers -----------------------------------------------------------

    def _wrap_epoch_fn(self, fn, b1: float):
        import jax
        import numpy as np

        def epoch(params, opt_state, buffers, xs, ys, rng):
            if self._stop:
                raise StopTraining("benchmark window closed")
            out = fn(params, opt_state, buffers, xs, ys, rng)
            cost = float(jax.block_until_ready(out[3]))
            now = time.monotonic()
            first = not self.epochs
            with self.event:
                self.epochs.append((now, int(np.asarray(xs).size)))
                self.costs.append(cost)
                self.event.notify_all()
            if first:
                import optax
                self.first_batch = (np.array(xs), np.array(ys))
                mu = optax.tree_utils.tree_get(out[1], "mu")
                self.first_grad = {k: np.asarray(v, np.float32) / (1.0 - b1)
                                   for k, v in mu.items()}
            return out

        return epoch

    def __enter__(self):
        from penroz_tpu.models.model import CompiledArch, NeuralNetworkModel
        spy = self
        make_epoch_fn = CompiledArch.train_epoch_fn
        serialize = NeuralNetworkModel.serialize

        def train_epoch_fn(arch, optimizer_config, *args, **kwargs):
            fn = make_epoch_fn(arch, optimizer_config, *args, **kwargs)
            (_, opt_args), = optimizer_config.items()
            b1 = float(opt_args.get("betas", (0.9, 0.999))[0])
            return spy._wrap_epoch_fn(fn, b1)

        def timed_serialize(model, sync_flush=False, tag=None):
            if spy._stop:
                return None     # the run is over: nothing reads this save
            t0 = time.monotonic()
            try:
                return serialize(model, sync_flush=sync_flush, tag=tag)
            finally:
                with spy.event:
                    spy.saves.append((t0, time.monotonic(), tag is not None))
                    spy.event.notify_all()

        CompiledArch.train_epoch_fn = train_epoch_fn
        NeuralNetworkModel.serialize = timed_serialize
        self._undo = [(CompiledArch, "train_epoch_fn", make_epoch_fn),
                      (NeuralNetworkModel, "serialize", serialize)]
        return self

    def __exit__(self, *exc):
        for owner, name, original in reversed(self._undo):
            setattr(owner, name, original)
