"""Fixed-size recurrent sequence state: gated linear-attention / SSD scan.

The second ``SequenceState`` backend (see ops/kv_cache.py for the protocol):
where the KV variants grow O(T) per row, the SSM state is a constant-size
per-row tensor ``(H, dk, dv)`` per layer, so rollback, preempt-resume,
disagg hand-off and hibernation all become fixed-size copies.

Recurrence (per head, per row; all math fp32):

    S_t = g_t * S_{t-1} + k_t ⊗ v_t          S in R^{dk×dv},  g_t = σ(gate_t)
    y_t = q_t · S_t                           q pre-scaled by dk^-0.5

Three execution forms, all bit-identical in greedy decoding because every
*cached* path uses the same sequential ``lax.scan`` token order:

- ``update_dense``  — cached prefill / batched decode: scan over T with a
  scalar-or-(B,) position offset (row views, batch generate, supersteps).
- ``update_packed`` — the unified ragged path: scan over the Tp packed slots
  of a ``build_descriptors`` block layout, read-modify-write per valid slot
  (mirrors ``PagedKVState.append_packed`` addressing).
- ``gla_full``      — no-cache training/eval of :class:`GatedSSM`: the
  sequential ``lax.scan`` over tokens wherever a gradient is taken and on
  the CPU (the serving kernel defines no VJP), the chunked Pallas kernel
  (ops/pallas/ssm_scan.py) on TPU inference.
- ``ssd_chunked``   — the same recurrence as a Mamba-2 mixer trains it
  (arXiv:2405.21060): a scalar decay a head, B and C shared by a group of
  heads, computed a chunk at a time with its own backward that keeps the
  chunk-boundary states and walks chunks, never tokens.

Checkpoint ring (exact spec-decode rollback): every token write also stores
the post-token state in a ring of ``ckpt_slots`` slots keyed by the *length
after the token* (``ckpt_pos``; −1 = empty).  ``rollback_row(row, L)``
restores the state checkpointed at length L (zeros for L == 0) and
invalidates slots from the discarded future — no replay, no page moves.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np


def ckpt_slots_default() -> int:
    """Ring size: enough for a spec-decode verify block plus slack."""
    slots = int(os.environ.get("PENROZ_SSM_CKPT", "8"))
    spec = int(os.environ.get("PENROZ_SPEC_DECODE", "0") or 0)
    return max(slots, spec + 2, 2)


def _outer(k_t, v_t):
    """k ⊗ v over trailing dims: (..., dk) x (..., dv) -> (..., dk, dv)."""
    return k_t[..., :, None] * v_t[..., None, :]


@jax.tree_util.register_pytree_node_class
class SSMState:
    """Per-row recurrent state for every ``ssm`` block of a model.

    Children: per-layer ``state`` (B, H, dk, dv) fp32, per-layer ``ckpt``
    (B, C, H, dk, dv) fp32 and ONE shared ``ckpt_pos`` (B, C) int32 (every
    layer checkpoints at the same positions, so the slot map is common).
    """

    def __init__(self, state, ckpt, ckpt_pos, specs, ckpt_slots):
        self.state = list(state)
        self.ckpt = list(ckpt)
        self.ckpt_pos = ckpt_pos
        self.specs = tuple(tuple(int(x) for x in s) for s in specs)
        self.ckpt_slots = int(ckpt_slots)

    # -- pytree -------------------------------------------------------------
    def tree_flatten(self):
        return ((tuple(self.state), tuple(self.ckpt), self.ckpt_pos),
                (self.specs, self.ckpt_slots))

    @classmethod
    def tree_unflatten(cls, aux, children):
        state, ckpt, ckpt_pos = children
        return cls(state, ckpt, ckpt_pos, aux[0], aux[1])

    # -- construction -------------------------------------------------------
    @classmethod
    def create(cls, specs, batch, ckpt_slots=None):
        """Zero state for ``specs = [(num_heads, head_dim, value_dim), ...]``."""
        C = int(ckpt_slots) if ckpt_slots else ckpt_slots_default()
        B = int(batch)
        state = [jnp.zeros((B, h, dk, dv), jnp.float32)
                 for (h, dk, dv) in specs]
        ckpt = [jnp.zeros((B, C, h, dk, dv), jnp.float32)
                for (h, dk, dv) in specs]
        ckpt_pos = jnp.full((B, C), -1, jnp.int32)
        return cls(state, ckpt, ckpt_pos, specs, C)

    @property
    def batch(self) -> int:
        return int(self.ckpt_pos.shape[0])

    def nbytes(self) -> int:
        n = self.ckpt_pos.size * self.ckpt_pos.dtype.itemsize
        for arr in (*self.state, *self.ckpt):
            n += arr.size * arr.dtype.itemsize
        return int(n)

    # -- SequenceState contract --------------------------------------------
    def reset(self):
        return SSMState([jnp.zeros_like(s) for s in self.state],
                        [jnp.zeros_like(c) for c in self.ckpt],
                        jnp.full_like(self.ckpt_pos, -1),
                        self.specs, self.ckpt_slots)

    def reset_row(self, row):
        state = [jax.lax.dynamic_update_slice_in_dim(
                     s, jnp.zeros_like(s[:1]), row, 0) for s in self.state]
        ckpt = [jax.lax.dynamic_update_slice_in_dim(
                    c, jnp.zeros_like(c[:1]), row, 0) for c in self.ckpt]
        ckpt_pos = jax.lax.dynamic_update_slice_in_dim(
            self.ckpt_pos, jnp.full_like(self.ckpt_pos[:1], -1), row, 0)
        return SSMState(state, ckpt, ckpt_pos, self.specs, self.ckpt_slots)

    def insert_row(self, row, src):
        """Copy a freshly prefilled batch-1 ``SSMState`` into row ``row``
        (the KV ``insert_row`` contract — admission of a newcomer)."""
        if src.specs != self.specs:
            raise ValueError(f"insert_row source specs {src.specs} != "
                             f"destination specs {self.specs}")
        return self.merge_row(row, src)

    def import_row(self, row, blob):
        """Install per-layer states for one row (hand-off / resume import).

        ``blob`` maps ``"state"`` to a list of (H, dk, dv) arrays (host numpy
        or device).  Checkpoints for the row start empty — the next decoded
        tokens repopulate the ring before any rollback can need them.
        """
        out = self.reset_row(row)
        state = [jax.lax.dynamic_update_slice_in_dim(
                     s, jnp.asarray(b, jnp.float32)[None], row, 0)
                 for s, b in zip(out.state, blob["state"])]
        return SSMState(state, out.ckpt, out.ckpt_pos,
                        self.specs, self.ckpt_slots)

    def rollback_row(self, row, new_length):
        """Exact rewind of one row to ``new_length`` via the checkpoint ring.

        Length 0 restores zeros.  A missing checkpoint keeps the current
        state (spec-decode writes every verified token into the ring, so
        the target length is always present there).
        """
        L = jnp.asarray(new_length, jnp.int32)
        pos_row = jax.lax.dynamic_slice_in_dim(self.ckpt_pos, row, 1, 0)[0]
        hit = pos_row == L  # at most one: slot v%C only ever stores value v
        any_hit = jnp.any(hit)
        state = []
        for l, s in enumerate(self.state):
            cur = jax.lax.dynamic_slice_in_dim(s, row, 1, 0)[0]
            ck = jax.lax.dynamic_slice_in_dim(self.ckpt[l], row, 1, 0)[0]
            restored = jnp.einsum("c,c...->...", hit.astype(ck.dtype), ck)
            sel = jnp.where(L == 0, jnp.zeros_like(cur),
                            jnp.where(any_hit, restored, cur))
            state.append(jax.lax.dynamic_update_slice_in_dim(
                s, sel[None], row, 0))
        # drop checkpoints from the discarded future (all of them at L == 0)
        inval = (pos_row > L) | (L == 0)
        pos_new = jnp.where(inval, jnp.int32(-1), pos_row)
        ckpt_pos = jax.lax.dynamic_update_slice_in_dim(
            self.ckpt_pos, pos_new[None], row, 0)
        return SSMState(state, self.ckpt, ckpt_pos,
                        self.specs, self.ckpt_slots)

    def row_view(self, row, length=None):
        """Batch-1 view of one row (rides KV ``row_view`` into jit bodies).
        ``length`` is accepted for contract uniformity and ignored — the
        recurrent state has no positional extent to re-clock."""
        state = [jax.lax.dynamic_slice_in_dim(s, row, 1, 0)
                 for s in self.state]
        ckpt = [jax.lax.dynamic_slice_in_dim(c, row, 1, 0)
                for c in self.ckpt]
        ckpt_pos = jax.lax.dynamic_slice_in_dim(self.ckpt_pos, row, 1, 0)
        return SSMState(state, ckpt, ckpt_pos, self.specs, self.ckpt_slots)

    def merge_row(self, row, view):
        state = [jax.lax.dynamic_update_slice_in_dim(s, vs, row, 0)
                 for s, vs in zip(self.state, view.state)]
        ckpt = [jax.lax.dynamic_update_slice_in_dim(c, vc, row, 0)
                for c, vc in zip(self.ckpt, view.ckpt)]
        ckpt_pos = jax.lax.dynamic_update_slice_in_dim(
            self.ckpt_pos, view.ckpt_pos, row, 0)
        return SSMState(state, ckpt, ckpt_pos, self.specs, self.ckpt_slots)

    def export_row(self, row, device: bool = False):
        """Constant-size blob for hand-off/hibernation: live state only."""
        arrs = [s[row] for s in self.state]
        if not device:
            arrs = [np.asarray(a) for a in arrs]
        return {"state": arrs, "specs": [list(s) for s in self.specs]}

    def export_row_pages(self, row, length, device: bool = False):
        """Contract alias for :meth:`export_row` — the "pages" of a
        recurrent row are its constant-size state blob; ``length`` is
        irrelevant to the export size (that is the whole point)."""
        return self.export_row(int(row), device=device)

    def import_row_pages(self, row, blob):
        """Contract alias for :meth:`import_row`."""
        return self.import_row(int(row), blob)

    def export_all(self, device: bool = False):
        """Whole-batch blob (full-cache hibernation path)."""
        state = self.state if device else [np.asarray(s) for s in self.state]
        return {"state": state, "specs": [list(s) for s in self.specs]}

    def import_all(self, blob):
        state = [jnp.asarray(b, jnp.float32) for b in blob["state"]]
        return SSMState(state, [jnp.zeros_like(c) for c in self.ckpt],
                        jnp.full_like(self.ckpt_pos, -1),
                        self.specs, self.ckpt_slots)

    # -- cached scan updates (mutating, like KV append_*) -------------------
    def update_dense(self, layer_idx, q, k, v, g, start):
        """Sequential scan over T for B rows at offset ``start`` (scalar or
        (B,)); mutates this layer's state + checkpoints, returns y
        (B, T, H, dv) fp32."""
        B, T = q.shape[0], q.shape[1]
        C = self.ckpt_slots
        start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
        pos_after = start[None, :] + jnp.arange(T, dtype=jnp.int32)[:, None] + 1
        rows = jnp.arange(B)
        xs = (q.swapaxes(0, 1).astype(jnp.float32),
              k.swapaxes(0, 1).astype(jnp.float32),
              v.swapaxes(0, 1).astype(jnp.float32),
              g.swapaxes(0, 1).astype(jnp.float32),
              pos_after)

        def step(carry, xt):
            s, ck, cp = carry
            q_t, k_t, v_t, g_t, pa = xt
            s = g_t[..., None, None] * s + _outer(k_t, v_t)
            y = jnp.einsum("bhk,bhkv->bhv", q_t, s)
            slot = pa % C
            ck = ck.at[rows, slot].set(s)
            cp = cp.at[rows, slot].set(pa)
            return (s, ck, cp), y

        carry = (self.state[layer_idx], self.ckpt[layer_idx], self.ckpt_pos)
        (s, ck, cp), ys = jax.lax.scan(step, carry, xs)
        self.state[layer_idx] = s
        self.ckpt[layer_idx] = ck
        self.ckpt_pos = cp
        return ys.swapaxes(0, 1)

    def update_packed(self, layer_idx, q, k, v, g, descs, block_q):
        """Sequential scan over the Tp packed slots of the unified ragged
        layout (descs: (NB, 4) [row, start, count, _]); q/k/v/g are
        (1, Tp, ...).  Invalid tail slots of each block are dropped via
        out-of-bounds scatter.  Returns y (1, Tp, H, dv) fp32."""
        B = self.ckpt_pos.shape[0]
        C = self.ckpt_slots
        Tp = q.shape[1]
        xs = (q[0].astype(jnp.float32), k[0].astype(jnp.float32),
              v[0].astype(jnp.float32), g[0].astype(jnp.float32),
              jnp.arange(Tp, dtype=jnp.int32))

        def step(carry, xt):
            st, ck, cp = carry
            q_p, k_p, v_p, g_p, p = xt
            blk = p // block_q
            t = p - blk * block_q
            row = descs[blk, 0]
            valid = t < descs[blk, 2]
            pa = descs[blk, 1] + t + 1
            s = jnp.take(st, row, axis=0)
            s_new = g_p[..., None, None] * s + _outer(k_p, v_p)
            y = jnp.einsum("hk,hkv->hv", q_p, s_new)
            srow = jnp.where(valid, row, B)  # B is out of bounds -> drop
            st = st.at[srow].set(s_new, mode="drop")
            slot = pa % C
            ck = ck.at[srow, slot].set(s_new, mode="drop")
            cp = cp.at[srow, slot].set(pa, mode="drop")
            return (st, ck, cp), y

        carry = (self.state[layer_idx], self.ckpt[layer_idx], self.ckpt_pos)
        (st, ck, cp), ys = jax.lax.scan(step, carry, xs)
        self.state[layer_idx] = st
        self.ckpt[layer_idx] = ck
        self.ckpt_pos = cp
        return ys[None]


# ---------------------------------------------------------------------------
# No-cache full-sequence form (training / uncached eval)
# ---------------------------------------------------------------------------

def gla_full_reference(q, k, v, g):
    """Sequential-scan oracle: exact recurrence, (B, T, H, ·) -> fp32."""
    B = q.shape[0]
    H, dk = q.shape[2], q.shape[3]
    dv = v.shape[-1]

    def step(s, xt):
        q_t, k_t, v_t, g_t = xt
        s = g_t[..., None, None] * s + _outer(k_t, v_t)
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    s0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    xs = tuple(t.swapaxes(0, 1).astype(jnp.float32) for t in (q, k, v, g))
    _, ys = jax.lax.scan(step, s0, xs)
    return ys.swapaxes(0, 1)


def gla_full(q, k, v, g, platform=None, training: bool = False):
    """Full causal gated linear attention, no cache.  TPU inference runs the
    chunked Pallas kernel; training and CPU run the differentiable scan
    oracle (the kernel defines no VJP)."""
    from penroz_tpu.ops import attention as attn_ops
    if not training and attn_ops._tpu_platform(q, platform):
        from penroz_tpu.ops.pallas import ssm_scan
        return attn_ops._on_shards(ssm_scan.gla_chunked, platform,
                                   ("b.h.", "b.h.", "b.h.", "b.h"), "b.h.",
                                   q, k, v, g)
    return gla_full_reference(q, k, v, g)


# ---------------------------------------------------------------------------
# Mamba-2's form (SSD, arXiv:2405.21060), trained: a scalar decay a head,
# B and C shared by a group of heads, a chunk at a time
# ---------------------------------------------------------------------------

def ssd_plan(T: int, heads: int, groups: int, head_dim: int, state: int,
             chunk: int, batch: int = 1) -> dict:
    """The static sizes of :func:`ssd_chunked` for ``T`` tokens: ``chunks``
    of ``chunk`` tokens (``T`` padded up to whole chunks with steps that
    change nothing), and the bytes of the float32 chunk-boundary states, the
    one thing the backward keeps beside what it was given.  ``path``:
    ``chunked`` is this file's ``jnp`` form, the only one written."""
    chunks = -(-T // chunk)
    return {"chunks": chunks, "padded": chunks * chunk - T, "path": "chunked",
            "boundary_bytes": 4 * batch * chunks * heads * head_dim * state}


def _ssd_log_decay(dt, A):
    """``(B, c, L, H)`` float32: the running sum inside each chunk of
    ``dt · A`` (inclusive), always <= 0."""
    return jnp.cumsum(dt * A, axis=2)


def _by_group(t, groups: int):
    """``(B, c, L, H, …)`` with its heads split as ``(groups, heads a
    group)``."""
    return t.reshape(t.shape[:3] + (groups, -1) + t.shape[4:])


def _ssd_chunk_states(x, dt, A, Bm):
    """What each chunk adds to the state by its end, from a zero state,
    ``(B, c, H, P, N)`` float32, and the decay over the whole chunk ``(B, c,
    H)``: ``Σ_s exp(l_end − l_s) dt_s x_s (x) B_s`` and ``exp(l_end)``."""
    G = Bm.shape[3]
    la = _ssd_log_decay(dt, A)
    w = jnp.exp(la[:, :, -1:, :] - la) * dt             # (B, c, L, H) f32
    xw = (x.astype(jnp.float32) * w[..., None]).astype(x.dtype)
    states = jnp.einsum("bcsgjp,bcsgn->bcgjpn", _by_group(xw, G), Bm,
                        preferred_element_type=jnp.float32)
    return (states.reshape(states.shape[:2] + (-1,) + states.shape[4:]),
            jnp.exp(la[:, :, -1, :]))


def _ssd_outputs(x, dt, A, Bm, Cm, s_in):
    """``y (B, c, L, H, P)`` float32 given each chunk's entering state
    ``s_in (B, c, H, P, N)``: inside a chunk the masked product with the
    decay differences ``exp(l_t − l_s)``, ``s <= t``, and the entering state
    read through ``C`` and decayed to ``t``."""
    G = Bm.shape[3]
    L = x.shape[2]
    la = _by_group(_ssd_log_decay(dt, A), G)            # (B, c, L, G, J)
    scores = jnp.einsum("bclgn,bcsgn->bcgls", Cm, Bm,
                        preferred_element_type=jnp.float32)
    below = jnp.tril(jnp.ones((L, L), bool), -1)
    # (B, c, G, J, L, S): exponents <= 0 where kept, so nothing overflows;
    # a token's own place is exp(0) by construction, not l_t − l_t, so that
    # the largest terms of the product take no part in the log-decays'
    # gradient, where they would cancel to rounding error
    diff = (la.transpose(0, 1, 3, 4, 2)[..., :, None]
            - la.transpose(0, 1, 3, 4, 2)[..., None, :])
    decay = jnp.exp(jnp.where(below, diff, jnp.where(jnp.eye(L, dtype=bool),
                                                      0.0, -jnp.inf)))
    mix = (scores[:, :, :, None] * decay
           * _by_group(dt, G).transpose(0, 1, 3, 4, 2)[..., None, :])
    xg = _by_group(x, G)
    y = jnp.einsum("bcgjls,bcsgjp->bclgjp", mix.astype(x.dtype), xg,
                   preferred_element_type=jnp.float32)
    sg = s_in.reshape(s_in.shape[:2] + (G, -1) + s_in.shape[3:])
    carried = jnp.einsum("bclgn,bcgjpn->bclgjp", Cm, sg.astype(Cm.dtype),
                         preferred_element_type=jnp.float32)
    y = y + carried * jnp.exp(la)[..., None]
    return y.reshape(x.shape)


def _ssd_entering_states(states, decay):
    """The state each chunk starts from, ``(B, c, H, P, N)``: zero, then
    ``S ← decay_c · S + states_c`` a chunk — ``c`` steps, not ``T``."""
    def step(S, part):
        added, d = part
        return d[..., None, None] * S + added, S

    _, entering = jax.lax.scan(
        step, jnp.zeros_like(states[:, 0]),
        (states.swapaxes(0, 1), decay.swapaxes(0, 1)))
    return entering.swapaxes(0, 1)


@jax.custom_vjp
def _ssd_core(x, dt, A, Bm, Cm):
    return _ssd_core_fwd(x, dt, A, Bm, Cm)[0]


def _ssd_core_fwd(x, dt, A, Bm, Cm):
    states, decay = _ssd_chunk_states(x, dt, A, Bm)
    s_in = _ssd_entering_states(states, decay)
    # kept: what the call was given and the chunk-boundary states
    return _ssd_outputs(x, dt, A, Bm, Cm, s_in), (x, dt, A, Bm, Cm, s_in)


def _ssd_core_bwd(kept, dy):
    """Chunks again, backwards: each chunk's inside is computed again from
    what was given and its entering state, and the cotangent of the state
    walks the ``c`` boundaries in reverse."""
    x, dt, A, Bm, Cm, s_in = kept
    _, pull_out = jax.vjp(_ssd_outputs, x, dt, A, Bm, Cm, s_in)
    dx, ddt, dA, dB, dC, ds_in = pull_out(dy)
    (_, decay), pull_states = jax.vjp(_ssd_chunk_states, x, dt, A, Bm)

    def step(g_next, part):
        # g_next: the cotangent of the state that leaves this chunk
        ds, d = part
        return ds + d[..., None, None] * g_next, g_next

    _, g_out = jax.lax.scan(
        step, jnp.zeros_like(ds_in[:, 0]),
        (ds_in.swapaxes(0, 1), decay.swapaxes(0, 1)), reverse=True)
    g_out = g_out.swapaxes(0, 1)
    ddecay = jnp.sum(g_out * s_in, axis=(-2, -1))
    dx2, ddt2, dA2, dB2 = pull_states((g_out, ddecay))
    return dx + dx2, ddt + ddt2, dA + dA2, dB + dB2, dC


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int = 128):
    """``y_t = S_t C_t`` of the recurrence ``S_t = exp(dt_t A) S_{t-1} +
    dt_t x_t ⊗ B_t``, float32, a chunk of ``chunk`` tokens at a time (the
    state-space duality of arXiv:2405.21060): inside a chunk matmuls,
    between chunks the ``(P, N)`` state carried.  ``x (B, T, H, P)``, ``dt
    (B, T, H)``, ``A (H,)`` negative, ``Bm``/``Cm (B, T, G, N)``, head
    ``h`` reading group ``h // (H/G)``; the token-by-token form is the
    benchmark's reference's (``reference/nemotron_h.py::ssd_recurrence``),
    which tier-1 holds this to, values and gradients.  Its derivative is
    its own (:func:`_ssd_core_bwd`): it keeps the chunk-boundary states
    (:func:`ssd_plan`'s ``boundary_bytes``) and neither a state a token nor
    ``T`` sequential steps.  The decay statistics (``dt``, the log-decays,
    their running sums, the state) are float32 whatever ``x``'s type; the
    products take ``x``'s, ``Bm``'s and ``Cm``'s type and accumulate in
    float32.  ``T`` is padded to whole chunks with steps of ``dt = 0``,
    which neither decay nor add."""
    B, T, H, P = x.shape
    G = Bm.shape[2]
    if H % G or Bm.shape != Cm.shape or dt.shape != (B, T, H):
        raise ValueError(f"ssd: x {x.shape}, dt {dt.shape}, B {Bm.shape}, "
                         f"C {Cm.shape} do not fit (heads a multiple of "
                         f"groups, dt a value a token and head)")
    pad = -T % chunk
    dt = dt.astype(jnp.float32)
    if pad:
        x, dt, Bm, Cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (t.ndim - 2)) for t in (x, dt, Bm, Cm))
    chunks = lambda t: t.reshape((B, -1, chunk) + t.shape[2:])
    y = _ssd_core(chunks(x), chunks(dt), A.astype(jnp.float32), chunks(Bm),
                  chunks(Cm))
    return y.reshape(B, T + pad, H, P)[:, :T]
