"""The token embedding's gradient (ops/modules.py::_gather_rows_bwd): both
ways to sum the cotangent's rows by id — XLA's scatter into an fp32 table on
one device, the one-hot scan under a mesh — against ``jnp.take``'s own
scatter-add VJP in float32 arithmetic; which one a placement gets; and the
counter that says so.  tests/test_tpu_compile.py asks the chip's compiler
what the scatter becomes there.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from penroz_tpu.ops import attention as A
from penroz_tpu.ops import modules as M
from penroz_tpu.parallel import mesh as mesh_lib

V, N, D = 100, 512, 16


def _ids(case: str, rng) -> np.ndarray:
    if case == "cycle64":          # the benchmark's shard: 64 ids, repeated
        return np.tile(rng.choice(V, 64, replace=False), N // 64)
    if case == "uniform":
        return rng.integers(0, V, N)
    if case == "all_equal":        # one row takes every token
        return np.full(N, 42)
    if case == "table_edges":
        return rng.choice([0, 1, V - 2, V - 1], N)
    if case == "rows_without_tokens":
        return rng.choice(np.r_[0:32, 48:V], N)
    if case == "past_a_scan_chunk":    # pads the one-hot scan's last chunk
        return rng.integers(0, V, M._GATHER_BWD_CHUNK + 37)
    if case == "ids_2d":
        return rng.integers(0, V, (4, N // 4))
    raise AssertionError(case)


@pytest.mark.parametrize("grad", [M._scatter_rows_grad, M._onehot_rows_grad])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", [
    "cycle64", "uniform", "all_equal", "table_edges", "rows_without_tokens",
    "past_a_scan_chunk", "ids_2d"])
def test_rows_grad_matches_take_vjp(case, dtype, grad):
    rng = np.random.default_rng(0)
    ids = jnp.asarray(_ids(case, rng), jnp.int32)
    g = jnp.asarray(rng.normal(size=ids.shape + (D,)), dtype)
    got = jax.jit(lambda ids, g: grad(ids, g, V, dtype))(ids, g)
    assert got.shape == (V, D) and got.dtype == dtype
    # float32 arithmetic: a bf16 scatter-add itself rounds at every add
    want = jax.grad(lambda t: (jnp.take(t, ids, axis=0)
                               * g.astype(jnp.float32)).sum())(
        jnp.zeros((V, D), jnp.float32))
    tol = dict(rtol=1e-5, atol=1e-4) if dtype == jnp.float32 else \
        dict(rtol=8e-3, atol=1e-2)      # one rounding of the sum to bf16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               **tol)


def test_repeated_ids_are_summed_in_fp32_not_in_the_tables_dtype():
    """What the custom backward is for: 512 equal bf16 rows of 1.0 sum to
    512 exactly; ``jnp.take``'s own VJP adds them one by one in bf16 and
    stalls at 256, where 1.0 is half a unit in the last place."""
    ids = jnp.zeros((512,), jnp.int32)
    table = jnp.zeros((8, D), jnp.bfloat16)

    def grad(lookup):
        return jax.grad(lambda t: lookup(t).astype(jnp.float32).sum())(table)

    ours = grad(lambda t: M._gather_rows(t, ids, 8, "bfloat16", "tpu"))
    naive = grad(lambda t: jnp.take(t, ids, axis=0))
    assert float(ours[0, 0]) == 512.0
    assert float(naive[0, 0]) < 512.0


def _meshed():
    return A.Placement("tpu", mesh_lib.make_mesh(jax.devices()[:4], model=1))


@pytest.mark.parametrize("shape", [
    (27, 32 * 3, 10),                 # the makemore MLP
    (512, 4 * 64, 64),                # the benchmark's rehearsal sizes
    (50304, 12 * 1024, 768),          # gpt2s-train-1chip's micro-batch
    (151936, 8 * 1024, 1024),         # a Qwen-sized vocabulary
])
def test_path_follows_the_placement_not_the_shape(shape, monkeypatch):
    """One device: the scatter, whatever the shape (on the chip it is never
    more than 0.22 ms behind the one-hot scan and up to 13 ms ahead:
    PERF.md §6, PR 30).  A mesh: the one-hot scan.  Nothing else is
    consulted."""
    num_rows, n, d = shape
    table = jax.ShapeDtypeStruct((num_rows, d), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((n,), jnp.int32)
    taken = []
    for name in ("_scatter_rows_grad", "_onehot_rows_grad"):
        monkeypatch.setattr(M, name, lambda *a, _n=name, _f=getattr(M, name):
                            taken.append(_n) or _f(*a))
    for platform in ("tpu", "cpu", None, _meshed()):
        jax.eval_shape(jax.grad(lambda t, ids: M._gather_rows(
            t, ids, num_rows, "bfloat16", platform).astype(
                jnp.float32).sum()), table, ids)
    assert taken == ["_scatter_rows_grad"] * 3 + ["_onehot_rows_grad"]


def test_embedding_layers_hand_their_placement_to_the_backward(monkeypatch):
    """``Embedding`` and ``ScaledEmbedding`` on a TPU placement go through
    ``_gather_rows`` with the context's hint; on the CPU through
    ``jnp.take``."""
    seen = []
    monkeypatch.setattr(M, "_gather_rows", lambda w, x, *rest: seen.append(
        rest) or jnp.take(w, x, axis=0))
    x = jnp.zeros((2, 4), jnp.int32)
    for layer in (M.Embedding(32, 8), M.ScaledEmbedding(32, 8, 3.0)):
        layer.bind("emb")
        params = layer.init(jax.random.key(0))
        for platform in ("tpu", "cpu"):
            layer.apply(x, M.Ctx(params, {}, platform=platform))
    assert seen == [(32, "float32", "tpu")] * 2


def test_embed_grad_plan_is_logged_once_and_spanned_per_trace(caplog):
    """The counter that says which backward engaged (as the flash plan's,
    tests/test_attention.py): one INFO line per distinct (shape, path), and
    a ``penroz/embed_grad_plan`` span under whatever span of a job's trace
    is compiling."""
    from penroz_tpu.utils import tracing
    num_rows, d = 50304, 768
    table = jax.ShapeDtypeStruct((num_rows, d), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((12, 1024), jnp.int32)
    meshed = _meshed()

    def loss(platform, t, ids):
        return M._gather_rows(t, ids, num_rows, "bfloat16",
                              platform).astype(jnp.float32).sum()

    M._log_grad_plan.cache_clear()
    tracing.reset()
    trace = tracing.maybe_trace("embed-plan-job", job=True, route="/train/")
    with caplog.at_level(logging.INFO, logger=M.__name__), \
            tracing.use(trace), tracing.span("penroz/train_dispatch"):
        for platform in ("tpu", "tpu", meshed):
            jax.eval_shape(jax.grad(lambda t, ids: loss(platform, t, ids)),
                           table, ids)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("embedding grad plan:")]
    assert lines == [
        "embedding grad plan: V=50304 N=12288 d=768 path=scatter",
        "embedding grad plan: V=50304 N=12288 d=768 path=onehot_scan"]
    dispatch = trace.to_dict()["root"]["children"][0]
    spans = [c["meta"] for c in dispatch["children"]
             if c["name"] == "penroz/embed_grad_plan"]
    assert [m["path"] for m in spans] == ["scatter", "scatter", "onehot_scan"]
    assert all((m["V"], m["N"], m["d"]) == (50304, 12288, 768) for m in spans)
    trace.finish("completed")
