"""A run with the timed path broken underneath ends with ``correct`` false.

A serving cell can have one of the faults a harness has to catch: a token
altered where it is produced.  No serving cell stands in ``BENCHMARK.json``
yet, so the cell is made of the files that are there, in a temporary copy
(``helpers.tree_with_a_serving_cell``).  The run is the harness's own
(``run.py``'s ``main`` with ``--rehearse``, which skips only the look for a
chip); the program's ``decode_mixed_step`` — the one dispatch every tick of
the served path makes — is wrapped so that tokens leave it altered, and the
comparison with the reference has to say so.  (The unbroken run of such a
cell ends ``correct`` true: ``test_add_cell.py``.)"""

import json
import os
import subprocess
import sys

import pytest

from helpers import ROOT, tree_with_a_serving_cell

BROKEN_RUN = r'''
import sys
sys.path.insert(0, {root!r})
from benchmark import run
from benchmark.lib import program

create = program.create_model


def create_then_break(cfg, seed, model_id):
    import jax.numpy as jnp
    from penroz_tpu.models.model import NeuralNetworkModel
    step = NeuralNetworkModel.decode_mixed_step
    vocab = int(cfg["vocab_size"])

    def altered(self, *args, **kw):
        sampled, kv = step(self, *args, **kw)
        return {alter}, kv
    NeuralNetworkModel.decode_mixed_step = altered
    return create(cfg, seed, model_id)


program.create_model = create_then_break
sys.exit(run.main(sys.argv[1:]))
'''

FAULTS = {
    "every_token": "(sampled + 1) % vocab",
    "a_quarter_of_the_tokens":
        "jnp.where(sampled % 4 == 0, (sampled + 1) % vocab, sampled)",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_altered_token_ends_the_run_not_correct(fault, tmp_path):
    cell = tree_with_a_serving_cell(tmp_path)
    script = tmp_path / "broken_run.py"
    script.write_text(BROKEN_RUN.format(root=str(tmp_path),
                                        alter=FAULTS[fault]))
    proc = subprocess.run(
        [sys.executable, str(script), "--workload", cell, "--seed",
         str(2**31 + 77), "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
    assert result["failed"] == 0 and result["attempted"] > 0
    failing = [name for name, c in result["checks"].items()
               if c["value"] > c["limit"]]
    assert failing, result["checks"]
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith("run.py: correct=False ")
